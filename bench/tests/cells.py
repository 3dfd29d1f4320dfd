"""Driving ``run.main`` and ``control.main`` on the CPU, in the test's
own process: the look for a TPU is stubbed here, not through an option of
the benchmark."""
import json

import jax

import run


def cpu_chips(cell):
    return jax.devices()[:cell["chips"]]


#: mixes ready in bench/ but not cells of the benchmark yet (PERF.md, open
#: questions); the tests drive them as cells
EXTRA = [
    {"name": "jamba-1.5-large-398b.sa", "config": "jamba-1.5-large-398b",
     "traffic": "annealing", "chips": 1},
    {"name": "stablelm-3b.bf", "config": "stablelm-3b",
     "traffic": "brute_force", "chips": 1},
    {"name": "stablelm-3b.bf.d4", "config": "stablelm-3b",
     "traffic": "brute_force_d4", "chips": 4},
]
D4 = EXTRA[2]


def with_cells(monkeypatch):
    """BENCHMARK.json as it is, with the ``EXTRA`` mixes as cells."""
    real = run._json

    def patched(path):
        data = real(path)
        if path.endswith("BENCHMARK.json"):
            have = {w["name"] for w in data["workloads"]}
            data = dict(data, workloads=data["workloads"] + [
                dict(c, why="a mix ready for a later cell") for c in EXTRA
                if c["name"] not in have])
        return data

    monkeypatch.setattr(run, "_json", patched)


def result(monkeypatch, capsys, workload, seed=4294967311, trace=0):
    """One round of ``workload`` (``--seconds 0``); the result line."""
    monkeypatch.setattr(run, "chips", cpu_chips)
    code = run.main(["--workload", workload, "--seed", str(seed),
                     "--seconds", "0", "--trace", str(trace)])
    out = capsys.readouterr().out.strip().splitlines()
    assert code == 0
    return json.loads(out[-1])


def workloads():
    """The benchmark's cells and the ready mixes, by name."""
    with open(run.os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    return names + [c["name"] for c in EXTRA if c["name"] not in names]
