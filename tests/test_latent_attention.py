"""Kimi-K2 as published: multi-head latent attention (MLA), a shared expert
beside 384 routed experts, a dense first layer.

The graph and parameter counts at the published sizes; the latent-state
sharding rule in all three cost-model engines; the engines' agreement on
the reduced model in prefill and decode; the zoo's latent attention
against a naive per-head formula and through its latent cache; and the
parser's span and node counters.
"""
import dataclasses
import random

import numpy as np
import pytest

from repro.configs import SHAPES_BY_NAME, get_arch, reduced
from repro.configs.base import ShapeSpec
from repro.core.backends import BACKENDS
from repro.core.graph_builder import build_hdgraph
from repro.core.hdgraph import HDGraph, Variables
from repro.core.objectives import Problem
from repro.core.perfmodel import ModelOptions, node_eval
from repro.core.platform import V5E_POD, Platform

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.accel.eval_jax import JaxEvaluator  # noqa: E402

KIMI = "kimi-k2-1t-a32b"
PLAT = Platform(name="t-4x4", mesh_axes=(("data", 4), ("model", 4)))
PREFILL = ShapeSpec("prefill_tiny", 256, 16, "prefill")
DECODE = ShapeSpec("decode_tiny", 256, 16, "decode")


def _problem(graph, backend="spmd", objective="throughput"):
    return Problem(graph=graph, platform=PLAT, backend=BACKENDS[backend],
                   objective=objective, exec_model="spmd",
                   opts=ModelOptions())


# ----------------------------------------------------------------------
# the published model
# ----------------------------------------------------------------------

@pytest.mark.parametrize("shape", ["prefill_32k", "decode_32k", "train_4k"])
def test_kimi_graph_at_published_sizes(shape):
    arch = get_arch(KIMI)
    g = build_hdgraph(arch, SHAPES_BY_NAME[shape])
    kinds = [n.kind for n in g.nodes]
    assert len(g.nodes) == 185
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "embed": 1, "mla": 61, "ffn": 1, "shared_expert": 60, "moe": 60,
        "norm": 1, "head": 1}
    assert kinds[:6] == ["embed", "mla", "ffn", "mla", "shared_expert",
                         "moe"]
    mla = [n for n in g.nodes if n.kind == "mla"]
    assert all(n.weight_bytes == 2 * 101_124_096 for n in mla)
    assert all(n.latent_kv and n.latent_dim == 512 and n.kv_limit == 0
               for n in mla)
    assert all(n.internal_rows == (shape == "decode_32k") for n in mla)
    ffn = next(n for n in g.nodes if n.kind == "ffn")
    shared = next(n for n in g.nodes if n.kind == "shared_expert")
    moe = next(n for n in g.nodes if n.kind == "moe")
    assert ffn.col_div == 18432 and shared.col_div == 2048
    assert moe.col_div == 384 and moe.ep_topk == 8
    assert moe.weight_bytes == 2 * (384 * 3 * 7168 * 2048 + 7168 * 384)
    # mla and the shared expert stack in scan groups of their own
    groups = {n.kind: n.scan_group for n in g.nodes if n.scan_group >= 0}
    assert len(set(groups.values())) == len(groups) == 4
    # cuts fall between layers (and after the embedding) only
    assert len(g.cut_edges) == 62
    for e in g.cut_edges:
        a, b = g.nodes[e], g.nodes[e + 1]
        assert a.layer != b.layer or a.kind == "embed"


def test_kimi_param_counts_match_the_published_sizes():
    arch = get_arch(KIMI)
    assert arch.mla_weights() == 101_124_096
    total = arch.param_count()
    assert total == 1_026_408_202_240
    assert abs(total - 1.04e12) / 1.04e12 < 0.02      # the card's 1.04T
    assert arch.active_param_count() == 32_861_470_720  # about 32B active


def test_latent_cache_bytes_and_decode_flops():
    arch = get_arch(KIMI)
    g = build_hdgraph(arch, SHAPES_BY_NAME["decode_32k"])
    mla = next(n for n in g.nodes if n.kind == "mla")
    # one 576-value bf16 vector a token: 128 x 32768 x 576 x 2 bytes
    assert mla.state_bytes == mla.kv_bytes == 128 * 32768 * 576 * 2
    D, H, qr, kvr, dn, dr, dv = 7168, 64, 1536, 512, 128, 64, 128
    absorbed = 2 * 128 * (D * qr + qr * H * (dn + dr) + D * (kvr + dr)
                          + H * dn * kvr + H * kvr * dv + H * dv * D) \
        + 2 * 128 * H * 32768 * (2 * kvr + dr)
    assert mla.flops == absorbed
    gp = build_hdgraph(arch, SHAPES_BY_NAME["prefill_32k"])
    mp = next(n for n in gp.nodes if n.kind == "mla")
    S = 32768
    assert mp.flops == 2 * 32 * S * (101_124_096 - qr - kvr) \
        + 2 * 32 * H * S * S * (dn + dr + dv) * 0.5
    assert mp.state_bytes == 32 * S * 576 * 2


# ----------------------------------------------------------------------
# the latent-state sharding rule, in all three engines
# ----------------------------------------------------------------------

def _latent_probe_graph(shape):
    """Reduced kimi with weightless latent nodes: a latent node's
    residency is then its cache share plus boundary buffers, which do not
    depend on ``s_out``."""
    g = build_hdgraph(reduced(get_arch(KIMI)), shape)
    nodes = [dataclasses.replace(n, weight_bytes=0.0) if n.kind == "mla"
             else n for n in g.nodes]
    return HDGraph(nodes, g.arch_name, g.shape_name, g.mode)


@pytest.mark.parametrize("shape", [PREFILL, DECODE], ids=lambda s: s.mode)
def test_latent_state_per_chip_is_the_same_at_every_s_out(shape):
    g = _latent_probe_graph(shape)
    prob = _problem(g)
    j = next(i for i, n in enumerate(g.nodes) if n.kind == "mla")
    node = g.nodes[j]
    assert node.state_bytes > 0
    n = len(g.nodes)
    designs = []
    for s_in in (1, 4):
        for s_out in (1, 2, 4):
            so = [1] * n
            si = [1] * n
            so[j], si[j] = s_out, s_in
            designs.append(Variables((), tuple(si), tuple(so), (1,) * n))
    scalar = np.array([[e.hbm_resident for e in prob.evaluate(v).node_evals]
                       for v in designs])[:, j]
    packed = prob.batched().pack(designs)
    numpy_ = prob.batched().evaluate_batch(*packed).node_resident[:, j]
    jax_ = JaxEvaluator.from_problem(prob).evaluate_batch(
        *packed).node_resident[:, j]
    for res in (scalar, numpy_, jax_):
        at = res.reshape(2, 3)          # [s_in][s_out]
        assert (at == at[:, :1]).all(), res
        assert (at[1] < at[0]).all()    # the sequence fold does divide it
    np.testing.assert_array_equal(scalar, numpy_)
    np.testing.assert_allclose(jax_, numpy_, rtol=1e-6)
    # a GQA attention node's cache does divide over s_out
    attn = build_hdgraph(reduced(get_arch("tinyllama-1.1b")), shape)
    a = next(x for x in attn.nodes if x.kind == "attn")
    r = [node_eval(a, 1, so, 1, PLAT, shape.mode).hbm_resident
         for so in (1, 2)]
    assert r[1] < r[0]


def test_strict_kv_does_not_cap_a_latent_node():
    g = build_hdgraph(reduced(get_arch(KIMI)), DECODE)
    j = next(i for i, n in enumerate(g.nodes) if n.kind == "mla")
    for name in ("megatron", "simple", "spmd"):
        be = BACKENDS[name]
        if "s_out" in be.fixed_unity:
            continue
        assert max(be.candidates(g, j, "s_out", PLAT)) == 4   # every head
    prob = _problem(g, backend="megatron")
    v = prob.backend.set_fold(g, prob.backend.initial(g).with_cuts(()),
                              j, "s_out", 4)
    assert v.s_out[j] == 4
    assert not [x for x in prob.check(v).violations if "kv_heads" in x]


# ----------------------------------------------------------------------
# engine agreement on the reduced model
# ----------------------------------------------------------------------

def _designs(prob, count, seed):
    rng = random.Random(seed)
    v = prob.backend.initial(prob.graph)
    out = []
    for _ in range(count):
        v = prob.backend.random_move(rng, prob.graph, v, prob.platform)
        out.append(v)
    return out


@pytest.mark.parametrize("shape", [PREFILL, DECODE], ids=lambda s: s.mode)
def test_reduced_kimi_engines_agree(shape):
    """Node by node the scalar, numpy and jax (float64) engines agree to
    the bit; objectives differ only by the order of partition sums."""
    graph = build_hdgraph(reduced(get_arch(KIMI)), shape)
    lat = [i for i, n in enumerate(graph.nodes) if n.kind == "mla"]
    with jax.enable_x64(True):
        prob = _problem(graph)
        designs = _designs(prob, 120, seed=7)
        # the sample folds the latent nodes' rows and heads both
        assert any(v.s_in[i] > 1 and v.s_out[i] > 1
                   for v in designs for i in lat)
        bev = prob.batched()
        packed = bev.pack(designs)
        rn = bev.evaluate_batch(*packed)
        rj = JaxEvaluator.from_problem(prob).evaluate_batch(*packed)
        evs = [prob.evaluate(v) for v in designs]
    for field in ("node_times", "node_resident", "node_collective"):
        np.testing.assert_array_equal(getattr(rj, field),
                                      getattr(rn, field), field)
    np.testing.assert_array_equal(
        rn.node_times, [[e.time for e in ev.node_evals] for ev in evs])
    np.testing.assert_array_equal(
        rn.node_resident, [[e.hbm_resident for e in ev.node_evals]
                           for ev in evs])
    np.testing.assert_array_equal(
        rn.node_collective, [[e.collective_bytes for e in ev.node_evals]
                             for ev in evs])
    np.testing.assert_array_equal(rj.feasible, rn.feasible)
    np.testing.assert_array_equal(rn.feasible, [ev.feasible for ev in evs])
    np.testing.assert_allclose(rj.objective, rn.objective, rtol=1e-12)
    np.testing.assert_allclose(rn.objective, [ev.objective for ev in evs],
                               rtol=1e-12)


def test_rule_based_descent_sees_moves_below_float32_resolution():
    """A move of a light node inside a partition whose time is dominated
    by a heavy one changes the partition's float32 total by less than its
    resolution. The float32 device descent still takes it, as the float64
    scalar descent does: probes are compared with the incumbent node by
    node, not total against total."""
    from repro.core.optimizers import rule_based
    g = build_hdgraph(reduced(get_arch("tinyllama-1.1b")), PREFILL)
    heavy = [dataclasses.replace(n, flops=n.flops * 1e9)
             if n.kind == "attn" else n for n in g.nodes]
    g = HDGraph(heavy, g.arch_name, g.shape_name, g.mode)
    a = rule_based(_problem(g), engine="numpy")
    b = rule_based(_problem(g), engine="jax")
    assert b.variables == a.variables
    assert b.points == a.points
    assert b.evaluation.objective == a.evaluation.objective


# ----------------------------------------------------------------------
# the zoo: latent attention and the shared expert
# ----------------------------------------------------------------------

def _rms(x, scale, eps=1e-5):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x: (S, d) with the rotary halves split, as ``layers.apply_rope``."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, d, 2) / d)
    ang = pos[:, None] * freqs[None, :]
    x1, x2 = x[:, :d // 2], x[:, d // 2:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x1 * np.sin(ang) + x2 * np.cos(ang)], axis=-1)


def _naive_mla(x, p, arch):
    """Latent attention written per head with the keys and values
    expanded, in float64: softmax(q k^T / sqrt(d_qk)) v for each head."""
    H, dn, dr, dv = (arch.num_heads, arch.qk_nope_head_dim,
                     arch.qk_rope_head_dim, arch.v_head_dim)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    B, S, _ = x.shape
    out = np.zeros_like(x)
    pos = np.arange(S, dtype=np.float64)
    for b in range(B):
        h = _rms(x[b], p["ln_scale"])
        c_q = _rms(h @ p["wq_a"], p["q_norm"])
        kv_a = h @ p["wkv_a"]
        c_kv = _rms(kv_a[:, :-dr], p["kv_norm"])
        k_rope = _rope(kv_a[:, -dr:], pos, arch.rope_theta)
        y = np.zeros((S, H * dv))
        for head in range(H):
            wq = p["wq_b"][:, head * (dn + dr):(head + 1) * (dn + dr)]
            q = c_q @ wq
            q = np.concatenate([q[:, :dn], _rope(q[:, dn:], pos,
                                                 arch.rope_theta)], -1)
            wkv = p["wkv_b"][:, head * (dn + dv):(head + 1) * (dn + dv)]
            k = np.concatenate([c_kv @ wkv[:, :dn], k_rope], -1)
            v = c_kv @ wkv[:, dn:]
            s = q @ k.T / np.sqrt(dn + dr)
            s = np.where(np.tril(np.ones((S, S), bool)), s, -np.inf)
            a = np.exp(s - s.max(-1, keepdims=True))
            y[:, head * dv:(head + 1) * dv] = (a / a.sum(-1, keepdims=True)) @ v
        out[b] = x[b] + y @ p["wo"]
    return out


def _mla_setup(S=8):
    from repro.models import attention as A
    arch = reduced(get_arch(KIMI))
    p = A.init_mla(jax.random.PRNGKey(3), arch.d_model, arch.num_heads,
                   arch.q_lora_rank, arch.kv_lora_rank,
                   arch.qk_nope_head_dim, arch.qk_rope_head_dim,
                   arch.v_head_dim, arch.norm, dtype=jnp.float32)
    # norm scales away from 1, so that they are exercised
    keys = jax.random.split(jax.random.PRNGKey(4), 3)
    for k, name in zip(keys, ("ln_scale", "q_norm", "kv_norm")):
        p[name] = 1.0 + 0.1 * jax.random.normal(k, p[name].shape)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, arch.d_model))
    kw = dict(num_heads=arch.num_heads,
              qk_nope_head_dim=arch.qk_nope_head_dim,
              qk_rope_head_dim=arch.qk_rope_head_dim,
              v_head_dim=arch.v_head_dim, norm=arch.norm,
              rope_theta=arch.rope_theta)
    return A, arch, p, x, kw


def test_latent_attention_matches_naive_per_head_formula():
    A, arch, p, x, kw = _mla_setup()
    S = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    with jax.default_matmul_precision("highest"):
        y, _ = A.attend_mla(x, p, positions=pos, **kw)
    want = _naive_mla(np.asarray(x, np.float64), p, arch)
    # float32 against float64 over a few chained projections
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-5, atol=2e-5)


def test_latent_attention_decode_through_the_cache_matches_full_forward():
    A, arch, p, x, kw = _mla_setup(S=9)
    B, S = x.shape[0], x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = {"c_kv": jnp.zeros((B, S, arch.kv_lora_rank), jnp.float32),
             "k_rope": jnp.zeros((B, S, arch.qk_rope_head_dim), jnp.float32)}
    with jax.default_matmul_precision("highest"):
        full, _ = A.attend_mla(x, p, positions=pos, **kw)
        _, cache = A.attend_mla(x[:, :S - 1], p, positions=pos[:, :S - 1],
                                cache=cache, cache_pos=jnp.int32(0), **kw)
        step, cache = A.attend_mla(x[:, S - 1:], p, positions=pos[:, S - 1:],
                                   cache=cache, cache_pos=jnp.int32(S - 1),
                                   **kw)
    assert set(cache) == {"c_kv", "k_rope"}
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-5, atol=1e-5)


def test_shared_expert_adds_a_dense_ffn_of_the_moe_input():
    """With the routed experts' output weights zeroed, the MoE block is
    the residual plus the shared expert's FFN of the block's normed
    input."""
    from repro.models import layers as L
    from repro.models import moe as M
    p = M.init_moe(jax.random.PRNGKey(0), 16, 8, 4, "swiglu", "rms",
                   dtype=jnp.float32, shared_d_ff=12)
    assert p["shared_w_up"].shape == (16, 12)
    p["w_down"] = jnp.zeros_like(p["w_down"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16))
    y = M.apply_moe(x, p, top_k=2, act="swiglu", norm="rms")
    shared = {k[len("shared_"):]: v for k, v in p.items()
              if k.startswith("shared_")}
    want = x + L.ffn_inner(shared, L.block_norm(x, p, "rms"), "swiglu",
                           x.dtype)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


def test_exporter_latent_cache_spec_never_shards_heads():
    from repro.core.exporter import export_plan
    arch = get_arch(KIMI)
    g = build_hdgraph(arch, SHAPES_BY_NAME["decode_32k"])
    n = len(g.nodes)
    v = Variables((), (16,) * n, (16,) * n, (1,) * n)
    plan = export_plan(g, v, V5E_POD)
    spec = plan.kv_cache_spec(0)
    assert len(spec) == 3 and spec[2] is None
    assert plan._boundary_kind(0).kind == "mla"


# ----------------------------------------------------------------------
# the parser's span and node counters
# ----------------------------------------------------------------------

def test_make_problem_records_the_parser_span_and_node_counts():
    from repro.core.pipeline import make_problem, optimise_mapping
    from repro.obs import metrics, trace
    arch = reduced(get_arch(KIMI))
    trace.enable()
    try:
        optimise_mapping(arch, PREFILL, platform=PLAT, optimiser="rule_based",
                         engine="numpy", exec_model="spmd")
    finally:
        trace.disable()
    spans = trace.snapshot()
    by_id = {s["id"]: s for s in spans}
    build = [s for s in spans if s["name"] == "graph.build"]
    assert len(build) == 1
    assert by_id[build[0]["parent"]]["name"] == "pipeline.make_problem"
    counters = metrics.snapshot()["counters"]
    assert counters["graph.nodes.mla"] == 4
    assert counters["graph.nodes.shared_expert"] == 3
    assert counters["graph.nodes.moe"] == 3
    make_problem(arch, DECODE, PLAT)           # one graph, counted once
    assert metrics.snapshot()["counters"]["graph.nodes.mla"] == 8


@pytest.mark.parametrize("shape", [PREFILL, DECODE], ids=lambda s: s.mode)
def test_portfolio_and_service_map_kimi_as_the_direct_call(shape):
    from repro.core.pipeline import optimise_mapping, optimise_portfolio
    from repro.service.server import MappingServer
    arch = reduced(get_arch(KIMI))
    kw = dict(optimiser="rule_based", exec_model="spmd", engine="jax")
    direct = optimise_mapping(arch, shape, PLAT, **kw)
    fleet = optimise_portfolio([arch, "tinyllama-1.1b"], shape, PLAT, **kw)
    with MappingServer() as srv:
        served = MappingServer.result(srv.submit(arch, shape, PLAT, **kw),
                                      timeout=300).plan
    for plan in (fleet[0], served):
        assert plan.objective_value == direct.objective_value
        assert [p.node_indices for p in plan.partitions] == \
            [p.node_indices for p in direct.partitions]
        assert [p.kinds for p in plan.partitions] == \
            [p.kinds for p in direct.partitions]
    assert any("mla" in p.kinds for p in direct.partitions)
