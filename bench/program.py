"""The benchmark's one door into the program under test.

Everything the benchmark takes from the program passes through here: the
user's entry point (``repro.core.pipeline.optimise_mapping``) and its
counters and spans (``repro.obs``).
Built from the configuration and traffic files alone: the architecture by
its registry name, the shape, platform and cost-model options from the
numbers the files state.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")


def importable() -> bool:
    """True when the program's sources sit beside the benchmark."""
    if not os.path.isdir(os.path.join(SRC, "repro")):
        return False
    if SRC not in sys.path:
        sys.path.insert(0, SRC)
    return True


def compilation_cache() -> Optional[str]:
    """The program's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR``
    or a fixed directory inside the checkout."""
    from repro import runtime_config
    return runtime_config.compilation_cache()


class Program:
    """One cell's calls into the program: a configuration and a mix."""

    def __init__(self, config: dict, traffic: dict):
        from repro.configs import get_arch
        from repro.configs.base import ShapeSpec
        from repro.core.perfmodel import ModelOptions
        from repro.core.platform import Platform
        from repro.obs import metrics

        self.config, self.traffic = config, traffic
        self.arch = get_arch(config["registry_arch"])
        p = config["platform"]
        self.platform = Platform(
            name=p["name"], mesh_axes=tuple((a, int(s))
                                            for a, s in p["mesh_axes"]),
            peak_flops=p["peak_flops"], hbm_bw=p["hbm_bw"],
            hbm_bytes=p["hbm_bytes"], ici_bw=p["ici_bw"], dma_bw=p["dma_bw"],
            reconf_fixed_s=p["reconf_fixed_s"])
        self.opts = ModelOptions(**config["model_options"])
        self.shapes = {v["shape"]: ShapeSpec(v["shape"], v["seq_len"],
                                             v["global_batch"], v["mode"])
                       for v in traffic["variants"]}
        self.optimiser = traffic["optimiser"]
        tag = f"optim.{self.optimiser}[{config['engine']}]"
        self._points = metrics.counter(f"{tag}.points")
        self._history = metrics.series(f"{tag}.convergence")
        self._metrics = metrics

    def request(self, variant: dict, kwargs: Dict[str, Any]) -> Dict[str, Any]:
        """One call of the user's entry point; the plan it returns with
        the points and the last device objective the program reported."""
        from repro.core.pipeline import optimise_mapping
        points0 = self._points.value
        hist0 = len(self._history.points)
        plan = optimise_mapping(
            self.arch, self.shapes[variant["shape"]], self.platform,
            backend=self.config["backend"],
            optimiser=self.optimiser, objective=variant["objective"],
            exec_model=self.config["exec_model"], opts=self.opts,
            engine=self.config["engine"], **kwargs)
        hist = self._history.points[hist0:]
        if len(self._history.points) > 1024:
            self._history.points.clear()
        return {"plan": plan, "points": self._points.value - points0,
                "device_objective": hist[-1][1] if hist else None}

    # -- telemetry --------------------------------------------------------
    def counters(self) -> Dict[str, int]:
        return dict(self._metrics.snapshot()["counters"])

    @staticmethod
    def spans_on(on: bool) -> None:
        from repro.obs import trace
        if on:
            trace.reset()
            trace.enable()
        else:
            trace.disable()

    @staticmethod
    def spans() -> List[dict]:
        from repro.obs import trace
        return trace.snapshot()
