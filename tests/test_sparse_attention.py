"""DeepSeek-V3.2 as published: DeepSeek sparse attention (DSA), three
dense layers, a shared expert beside 256 routed experts.

The graph and parameter counts at the published sizes; the three rules
the ``dsa`` node adds, in all three cost-model engines; the engines'
agreement on the reduced model in prefill and decode; the sparse terms
kept out of the records and traces of graphs without ``dsa`` nodes; the
normal path (optimisers, engines, portfolio, service, exporter); and the
zoo's sparse attention against a naive per-query formula, through its two
caches, and against the FLOPs the node prices.
"""
import dataclasses
import math
import random

import numpy as np
import pytest

from repro.configs import get_arch, reduced
from repro.configs.base import ShapeSpec
from repro.core.backends import BACKENDS
from repro.core.graph_builder import build_hdgraph, sparse_selected
from repro.core.hdgraph import Variables
from repro.core.objectives import Problem
from repro.core.perfmodel import ModelOptions, node_eval
from repro.core.platform import V5E_POD, Platform

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.core.accel.eval_jax import JaxEvaluator  # noqa: E402

DSV = "deepseek-v3.2-671b-a37b"
PLAT = Platform(name="t-4x4", mesh_axes=(("data", 4), ("model", 4)))
PREFILL = ShapeSpec("prefill_tiny", 256, 16, "prefill")
DECODE = ShapeSpec("decode_tiny", 256, 16, "decode")
PREFILL_128K = ShapeSpec("prefill_128k", 131072, 8, "prefill")
DECODE_128K = ShapeSpec("decode_128k", 131072, 128, "decode")
DSA_LEAVES = ("m_dsa", "indexer_flops", "indexer_weight_bytes",
              "state_io_bytes", "index_topk")


def _problem(graph, objective="throughput", platform=PLAT):
    return Problem(graph=graph, platform=platform, backend=BACKENDS["spmd"],
                   objective=objective, exec_model="spmd",
                   opts=ModelOptions())


def _dsa(graph):
    return [i for i, n in enumerate(graph.nodes) if n.kind == "dsa"]


# ----------------------------------------------------------------------
# the published model
# ----------------------------------------------------------------------

@pytest.mark.parametrize("shape", [PREFILL_128K, DECODE_128K],
                         ids=lambda s: s.name)
def test_graph_at_published_sizes(shape):
    g = build_hdgraph(get_arch(DSV), shape)
    kinds = [n.kind for n in g.nodes]
    assert len(g.nodes) == 183
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "embed": 1, "dsa": 61, "ffn": 3, "shared_expert": 58, "moe": 58,
        "norm": 1, "head": 1}
    assert kinds[:10] == ["embed", "dsa", "ffn", "dsa", "ffn", "dsa", "ffn",
                          "dsa", "shared_expert", "moe"]
    dsa = [g.nodes[i] for i in _dsa(g)]
    assert all(n.latent_kv and n.latent_dim == 512 and n.kv_limit == 0
               and n.col_div == 128 for n in dsa)
    assert all(n.internal_rows == (shape.mode == "decode") for n in dsa)
    assert all(n.indexer_weight_bytes == 2 * 13_959_424 for n in dsa)
    assert all(n.weight_bytes == 2 * (187_107_328 + 13_959_424)
               for n in dsa)
    moe = next(n for n in g.nodes if n.kind == "moe")
    assert moe.col_div == 256 and moe.ep_topk == 8
    assert len(g.cut_edges) == 62


def test_param_counts_match_the_published_sizes():
    arch = get_arch(DSV)
    assert arch.mla_weights() == 187_107_328
    # 1536x64x128 + 7168x128 + a LayerNorm of 128 + 7168x64
    assert arch.indexer_weights() == 13_959_424
    total = arch.param_count()
    # the published 671B main model plus 61 indexers of about 14.0M
    assert total == 671_877_922_048
    assert total - 61 * 13_959_424 == 671_026_397_184
    assert abs(total - 671.9e9) / 671.9e9 < 1e-4


def test_dsa_node_flops_and_bytes_at_published_sizes():
    D, H, qr, kvr, dn, dr, dv = 7168, 128, 1536, 512, 128, 64, 128
    HI, dI, K, L = 64, 128, 2048, 131072
    proj = D * qr + qr * H * (dn + dr) + D * (kvr + dr) + H * dn * kvr \
        + H * kvr * dv + H * dv * D
    idx_proj = qr * HI * dI + D * dI + D * HI
    gp = build_hdgraph(get_arch(DSV), PREFILL_128K)
    p = gp.nodes[_dsa(gp)[0]]
    B = 8
    ksel = K * (K + 1) // 2 + (L - K) * K
    assert sparse_selected(K, L, L, False) == ksel
    assert p.indexer_flops == 2 * B * L * idx_proj \
        + 2 * B * HI * dI * L * (L + 1) // 2
    assert p.flops == p.indexer_flops + 2 * B * L * proj \
        + 2 * B * H * ksel * (2 * kvr + dr)
    # the motivation's prefill numbers: indexer scores 1.13e15, core 5.9e14
    assert round(2 * B * HI * dI * L * (L + 1) / 2 / 1e13) == 113
    assert round(2 * B * H * ksel * (2 * kvr + dr) / 1e13) == 59
    caches = B * L * (kvr + dr) * 2 + B * L * (dI + 4)
    assert p.state_bytes == p.kv_bytes == p.state_io_bytes == caches
    gd = build_hdgraph(get_arch(DSV), DECODE_128K)
    d = gd.nodes[_dsa(gd)[0]]
    B = 128
    assert d.flops == 2 * B * (idx_proj + proj) + 2 * B * HI * dI * L \
        + 2 * B * H * K * (2 * kvr + dr)
    keys = B * L * (dI + 4)
    assert d.state_bytes == B * L * (kvr + dr) * 2 + keys
    # a step reads the index keys and the selected latent rows: 2.2 GB
    # and 0.30 GB against the 19.3 GB dense latent cache
    assert d.state_io_bytes == keys + B * K * (kvr + dr) * 2
    assert round(keys / 1e8) == 22 and round(B * K * 576 * 2 / 1e7) == 30
    assert d.index_topk == K and d.rows == L


# ----------------------------------------------------------------------
# rules a-c, in all three engines
# ----------------------------------------------------------------------

BIG = Platform(name="t-32x32", mesh_axes=(("data", 32), ("model", 32)))
FOLDS = [(2, 64, 2), (2, 128, 2), (4, 64, 4), (4, 128, 4)]


def _expected(n, si, so, kk, mode):
    """NodeEval terms of a ``dsa`` node by the three rules, written out."""
    c = si * so * kk
    compute = ((n.flops - n.indexer_flops) / c
               + n.indexer_flops / (si * kk)) / (BIG.peak_flops * 0.72)
    w = (n.weight_bytes - n.indexer_weight_bytes) / so \
        + n.indexer_weight_bytes
    b_in = 1 if n.internal_rows else si
    hbm = n.act_bytes / (b_in * kk) + n.inner_bytes / c + w \
        + n.state_io_bytes / (kk * si)
    rows = 1 if mode == "decode" else n.rows
    resident = w + n.state_bytes / (kk * si) \
        + 2.0 * n.batch * rows * n.fm_width * 2 / (b_in * kk)
    shard = n.batch * rows * n.fm_width * 2 / (b_in * kk)
    coll = 2.0 * (so - 1) / so * shard
    if mode == "decode":
        coll += n.batch / kk * n.cols / so * (n.latent_dim + 2.0) * 4.0 \
            * (si - 1) / si
        coll += n.batch / kk * n.index_topk * 8.0 * (si - 1)
    else:
        coll += n.kv_bytes / kk * (si - 1) / si
    return compute, hbm, resident, coll


@pytest.mark.parametrize("shape", [PREFILL_128K, DECODE_128K],
                         ids=lambda s: s.name)
@pytest.mark.parametrize("folds", FOLDS, ids=str)
def test_rules_a_to_c_in_every_engine(shape, folds):
    si, so, kk = folds
    g = build_hdgraph(get_arch(DSV), shape)
    j = _dsa(g)[0]
    node = g.nodes[j]
    compute, hbm, resident, coll = _expected(node, si, so, kk, shape.mode)
    e = node_eval(node, si, so, kk, BIG, shape.mode)
    assert e.compute_s == pytest.approx(compute, rel=1e-12)
    assert e.hbm_bytes == pytest.approx(hbm, rel=1e-12)
    assert e.hbm_resident == pytest.approx(resident, rel=1e-12)
    assert e.collective_bytes == pytest.approx(coll, rel=1e-12)
    n = len(g.nodes)
    si_, so_, kk_ = [1] * n, [1] * n, [1] * n
    for i in _dsa(g):
        si_[i], so_[i], kk_[i] = si, so, kk
    v = Variables((), tuple(si_), tuple(so_), tuple(kk_))
    with jax.enable_x64(True):
        prob = _problem(g, platform=BIG)
        packed = prob.batched().pack([v])
        rn = prob.batched().evaluate_batch(*packed)
        rj = JaxEvaluator.from_problem(prob).evaluate_batch(*packed)
    for r in (rn, rj):
        assert r.node_resident[0, j] == e.hbm_resident
        assert r.node_collective[0, j] == e.collective_bytes
        assert r.node_times[0, j] == e.time


def test_rule_a_indexer_whole_on_every_head_shard():
    """Between s_out 64 and 128 only the core's FLOPs and weights halve."""
    g = build_hdgraph(get_arch(DSV), DECODE_128K)
    n = g.nodes[_dsa(g)[0]]
    e64, e128 = (node_eval(n, 2, so, 2, BIG, "decode") for so in (64, 128))
    core_w = n.weight_bytes - n.indexer_weight_bytes
    assert e64.hbm_resident - e128.hbm_resident == pytest.approx(
        core_w / 128, rel=1e-12)
    peak = BIG.peak_flops * 0.72
    assert (e64.compute_s - e128.compute_s) * peak == pytest.approx(
        (n.flops - n.indexer_flops) / (2 * 2 * 128), rel=1e-9)
    # the indexer's share of a chip's FLOPs never falls with the head fold
    assert n.indexer_flops / 4 < e128.compute_s * peak


def test_rule_b_decode_reads_keys_and_selected_rows_only():
    g = build_hdgraph(get_arch(DSV), DECODE_128K)
    # weightless, so that only the state terms differ
    n = dataclasses.replace(g.nodes[_dsa(g)[0]], weight_bytes=0.0,
                            indexer_weight_bytes=0.0)
    dense = dataclasses.replace(n, kind="mla", state_io_bytes=0.0)
    es, ed = (node_eval(x, 4, 64, 4, BIG, "decode") for x in (n, dense))
    # the dense latent node reads both whole caches a step
    assert ed.hbm_bytes - es.hbm_bytes == pytest.approx(
        (n.state_bytes - n.state_io_bytes) / 16, rel=1e-9)
    assert es.hbm_resident == ed.hbm_resident       # both caches resident


def test_rule_c_top_k_merge_only_with_split_kv():
    g = build_hdgraph(get_arch(DSV), DECODE_128K)
    n = g.nodes[_dsa(g)[0]]
    dense = dataclasses.replace(n, kind="mla")
    for si in (1, 2, 8):
        gap = node_eval(n, si, 64, 4, BIG, "decode").collective_bytes \
            - node_eval(dense, si, 64, 4, BIG, "decode").collective_bytes
        assert gap == pytest.approx(128 / 4 * 2048 * 8 * (si - 1), rel=1e-12)
    # prefill: the ring carries the index keys beside the latent cache
    gp = build_hdgraph(get_arch(DSV), PREFILL_128K)
    p = gp.nodes[_dsa(gp)[0]]
    keys = 8 * 131072 * (128 + 4)
    assert p.kv_bytes - keys == 8 * 131072 * 576 * 2
    ring = node_eval(p, 4, 1, 2, BIG, "prefill").collective_bytes
    assert ring == pytest.approx(p.kv_bytes / 2 * 3 / 4, rel=1e-12)


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
def test_reduced_model_engines_agree(shape):
    """Node by node the scalar, numpy and jax (float64) engines agree to
    the bit; objectives differ only by the order of partition sums."""
    graph = build_hdgraph(reduced(get_arch(DSV)), shape)
    dsa = _dsa(graph)
    with jax.enable_x64(True):
        prob = _problem(graph)
        designs = _designs(prob, 150, seed=7)
        # the sample folds the sparse nodes' rows, heads and batch
        assert any(v.s_in[i] > 1 and v.s_out[i] > 1
                   for v in designs for i in dsa)
        assert any(v.kern[i] > 1 for v in designs for i in dsa)
        bev = prob.batched()
        packed = bev.pack(designs)
        rn = bev.evaluate_batch(*packed)
        rj = JaxEvaluator.from_problem(prob).evaluate_batch(*packed)
        evs = [prob.evaluate(v) for v in designs]
    for field in ("node_times", "node_resident", "node_collective"):
        np.testing.assert_array_equal(getattr(rj, field),
                                      getattr(rn, field), field)
    for field, attr in (("node_times", "time"),
                        ("node_resident", "hbm_resident"),
                        ("node_collective", "collective_bytes")):
        np.testing.assert_array_equal(
            getattr(rn, field),
            [[getattr(e, attr) for e in ev.node_evals] for ev in evs])
    np.testing.assert_array_equal(rj.feasible, rn.feasible)
    np.testing.assert_array_equal(rn.feasible, [ev.feasible for ev in evs])
    np.testing.assert_allclose(rj.objective, rn.objective, rtol=1e-12)
    np.testing.assert_allclose(rn.objective, [ev.objective for ev in evs],
                               rtol=1e-12)
    np.testing.assert_allclose(rn.reconf_time,
                               [ev.reconf_time for ev in evs], rtol=1e-12)


# ----------------------------------------------------------------------
# the sparse terms stay out of graphs without dsa nodes
# ----------------------------------------------------------------------

def test_graph_without_dsa_carries_no_sparse_leaves():
    from repro.core.accel.lowering import build_static_spec, lower_program
    kimi = _problem(build_hdgraph(reduced(get_arch("kimi-k2-1t-a32b")),
                                  DECODE))
    bev = kimi.batched()
    assert not bev.static.dsa
    assert all(getattr(bev.arrays, f) is None for f in DSA_LEAVES)
    static, A = lower_program(bev)
    assert not static.dsa
    assert all(getattr(A, f) is None for f in DSA_LEAVES)
    assert static == build_static_spec(bev)
    sparse = _problem(build_hdgraph(reduced(get_arch(DSV)), DECODE))
    assert sparse.batched().static.dsa
    A = sparse.batched().arrays
    assert A.m_dsa.tolist() == [n.kind == "dsa" for n in sparse.graph.nodes]


@pytest.mark.parametrize("shape", [PREFILL, DECODE], ids=lambda s: s.mode)
def test_zero_sparse_leaves_change_nothing(shape):
    """A member without dsa nodes in a stack that has the terms on
    evaluates bit for bit as alone."""
    g = build_hdgraph(reduced(get_arch("kimi-k2-1t-a32b")), shape)
    with jax.enable_x64(True):
        prob = _problem(g)
        packed = prob.batched().pack(_designs(prob, 60, seed=3))
        alone = JaxEvaluator.from_problem(prob)
        on = JaxEvaluator.from_problem(prob, pad_dsa=True)
        assert on.static.dsa and not alone.static.dsa
        assert not np.asarray(on.arrays.m_dsa).any()
        ra, ro = alone.evaluate_batch(*packed), on.evaluate_batch(*packed)
    for field in ("objective", "feasible", "node_times", "node_resident",
                  "node_collective", "reconf_time"):
        np.testing.assert_array_equal(getattr(ro, field), getattr(ra, field))


# ----------------------------------------------------------------------
# the normal path
# ----------------------------------------------------------------------

@pytest.mark.parametrize("optimiser,kw", [
    ("rule_based", {}), ("brute_force", {"max_points": 2048}),
    ("annealing", {"max_iters": 40, "seed": 1})],
    ids=["rule_based", "brute_force", "annealing"])
def test_optimisers_map_the_reduced_model_on_both_engines(optimiser, kw):
    from repro.core.pipeline import optimise_mapping
    arch = reduced(get_arch(DSV))
    plans = [optimise_mapping(arch, PREFILL, PLAT, optimiser=optimiser,
                              exec_model="spmd", engine=engine, **kw)
             for engine in ("numpy", "jax")]
    for plan in plans:
        assert any("dsa" in p.kinds for p in plan.partitions)
        assert sum(len(p.node_indices) for p in plan.partitions) == 15
    if optimiser != "annealing":
        assert plans[0].objective_value == pytest.approx(
            plans[1].objective_value, rel=1e-5)
        assert [p.kinds for p in plans[0].partitions] == \
            [p.kinds for p in plans[1].partitions]


def test_make_problem_records_the_sparse_node_count():
    from repro.core.pipeline import make_problem
    from repro.obs import metrics
    before = metrics.snapshot()["counters"].get("graph.nodes.dsa", 0)
    make_problem(get_arch(DSV), DECODE_128K, V5E_POD)
    after = metrics.snapshot()["counters"]["graph.nodes.dsa"]
    assert after - before == 61


@pytest.mark.parametrize("shape", [PREFILL, DECODE], ids=lambda s: s.mode)
def test_portfolio_and_service_map_the_model_as_the_direct_call(shape):
    from repro.core.pipeline import optimise_mapping, optimise_portfolio
    from repro.service.server import MappingServer
    arch = reduced(get_arch(DSV))
    kw = dict(optimiser="rule_based", exec_model="spmd", engine="jax")
    direct = optimise_mapping(arch, shape, PLAT, **kw)
    fleet = optimise_portfolio([arch, "tinyllama-1.1b"], shape, PLAT, **kw)
    alone = optimise_mapping(get_arch("tinyllama-1.1b"), shape,
                             PLAT, **kw)
    with MappingServer() as srv:
        served = MappingServer.result(srv.submit(arch, shape, PLAT, **kw),
                                      timeout=300).plan
    for plan in (fleet[0], served):
        assert plan.objective_value == direct.objective_value
        assert [p.node_indices for p in plan.partitions] == \
            [p.node_indices for p in direct.partitions]
        assert [p.kinds for p in plan.partitions] == \
            [p.kinds for p in direct.partitions]
    # the member without dsa nodes stacked beside it answers as alone
    assert fleet[1].objective_value == alone.objective_value
    assert any("dsa" in p.kinds for p in direct.partitions)


def test_exporter_cache_specs_never_shard_heads():
    from jax.sharding import PartitionSpec as P

    from repro.core.exporter import export_plan
    from repro.models.model import Model
    g = build_hdgraph(get_arch(DSV), DECODE_128K)
    n = len(g.nodes)
    plan = export_plan(g, Variables((), (16,) * n, (16,) * n, (1,) * n),
                       V5E_POD)
    assert plan._boundary_kind(0).kind == "dsa"
    spec = plan.kv_cache_spec(0)
    assert len(spec) == 3 and spec[1] is not None and spec[2] is None
    model = Model(reduced(get_arch(DSV)))
    blocks = [b for seg in model.cache_specs(plan).values()
              for pk, b in seg.items() if pk.endswith("_dsa")]
    assert len(blocks) == 4     # a segment per dense layer, one for the rest
    for caches in blocks:
        assert set(caches) == {"c_kv", "k_rope", "index_keys"}
        for c in caches.values():
            assert c == P(None, *spec)
    # the indexer's weights are whole on every head shard, the core's
    # query and kv up-projections shard over the head axes
    blocks = [b for seg in model.param_specs(plan).values()
              if isinstance(seg, dict)
              for pk, b in seg.items() if pk.endswith("_dsa")]
    for params in blocks:
        for name in ("wq_idx", "wk_idx", "w_idx", "k_idx_scale",
                     "k_idx_bias"):
            assert all(a is None for a in params[name]), name
        assert params["wq_b"][-1] is not None


# ----------------------------------------------------------------------
# the zoo: sparse attention against a naive per-query formula
# ----------------------------------------------------------------------

KW = dict(num_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
          index_n_heads=8, index_head_dim=16, norm="rms", rope_theta=10000.0)


def _setup(S=32, topk=8):
    from repro.models import attention as A
    arch = reduced(get_arch(DSV))
    p = A.init_dsa(jax.random.PRNGKey(3), arch.d_model, 4, 48, 32, 16, 8, 16,
                   8, 16, arch.norm, dtype=jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    for k, name in zip(keys, ("ln_scale", "q_norm", "kv_norm",
                              "k_idx_scale", "k_idx_bias")):
        base = 0.0 if name == "k_idx_bias" else 1.0
        p[name] = base + 0.1 * jax.random.normal(k, p[name].shape)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, S, arch.d_model))
    return A, p, x, dict(KW, v_head_dim=16, index_topk=topk)


def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _ln(x, scale, bias, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _rope(x, pos, theta):
    """x: (S, d) with the rotary halves split, as ``layers.apply_rope``."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * freqs[None, :]
    x1, x2 = x[:, :d // 2], x[:, d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _naive(x, p, topk):
    """Query by query in float32: the indexer score I(t, s) head by head,
    the top min(K, t + 1) positions s <= t (ties to the lower position),
    and per-head softmax attention over the selected rows in the
    non-absorbed form (keys and values expanded from the latent)."""
    H, dn, dr, dv, HI, dI = 4, 16, 8, 16, 8, 16
    B, S, _ = x.shape
    pos = jnp.arange(S, dtype=jnp.float32)
    outs, sels = [], []
    for b in range(B):
        h = _rms(x[b], p["ln_scale"])
        c_q = _rms(h @ p["wq_a"], p["q_norm"])
        kv_a = h @ p["wkv_a"]
        c_kv = _rms(kv_a[:, :-dr], p["kv_norm"])
        k_rope = _rope(kv_a[:, -dr:], pos, 10000.0)
        q_i = (c_q @ p["wq_idx"]).reshape(S, HI, dI)
        k_i = _ln(h @ p["wk_idx"], p["k_idx_scale"], p["k_idx_bias"])
        w_i = h @ p["w_idx"]
        y = jnp.zeros((S, H * dv))
        rows = []
        for t in range(S):
            score = jnp.zeros(t + 1)
            for j in range(HI):
                score = score + w_i[t, j] * jax.nn.relu(k_i[:t + 1] @ q_i[t, j])
            order = sorted(range(t + 1), key=lambda s: (-float(score[s]), s))
            sel = np.array(order[:min(topk, t + 1)])
            rows.append(sel)
            for head in range(H):
                wq = p["wq_b"][:, head * (dn + dr):(head + 1) * (dn + dr)]
                q = c_q[t] @ wq
                q = jnp.concatenate([q[:dn], _rope(q[None, dn:], pos[t:t + 1],
                                                   10000.0)[0]])
                wkv = p["wkv_b"][:, head * (dn + dv):(head + 1) * (dn + dv)]
                k = jnp.concatenate([c_kv[sel] @ wkv[:, :dn], k_rope[sel]], -1)
                v = c_kv[sel] @ wkv[:, dn:]
                a = jax.nn.softmax(k @ q / math.sqrt(dn + dr))
                y = y.at[t, head * dv:(head + 1) * dv].set(a @ v)
        outs.append(x[b] + y @ p["wo"])
        sels.append(rows)
    return jnp.stack(outs), sels


def test_prefill_matches_the_naive_per_query_formula():
    A, p, x, kw = _setup()
    S = x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (2, S))
    with jax.default_matmul_precision("highest"):
        y, _ = A.attend_dsa(x, p, positions=pos, **kw)
        sel = A.dsa_prefill_selection(
            x, p, index_topk=8, positions=pos,
            **{k: v for k, v in KW.items()})
        want, want_sel = _naive(x, p, 8)
    sel = np.asarray(sel)
    for b in range(2):
        for t in range(S):
            got = sel[b, t][sel[b, t] >= 0]
            assert set(got.tolist()) == set(want_sel[b][t].tolist()), (b, t)
    # selection really drops positions: query t keeps min(8, t + 1)
    assert (sel >= 0).sum(-1).tolist() == [[min(8, t + 1)
                                            for t in range(S)]] * 2
    # float32 against float32 through a few chained projections, summed
    # in another order: a few float32 ulps of the unit-scale outputs
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_prefill_then_decode_through_both_caches_matches_full_forward():
    A, p, x, kw = _setup(S=33)
    B, S = x.shape[0], x.shape[1]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    cache = {"c_kv": jnp.zeros((B, S, 32)), "k_rope": jnp.zeros((B, S, 8)),
             "index_keys": jnp.zeros((B, S, 16))}
    with jax.default_matmul_precision("highest"):
        full, _ = A.attend_dsa(x, p, positions=pos, **kw)
        pre, cache = A.attend_dsa(x[:, :S - 1], p, positions=pos[:, :S - 1],
                                  cache=cache, cache_pos=jnp.int32(0), **kw)
        step, cache = A.attend_dsa(x[:, S - 1:], p, positions=pos[:, S - 1:],
                                   cache=cache, cache_pos=jnp.int32(S - 1),
                                   **kw)
    assert set(cache) == {"c_kv", "k_rope", "index_keys"}
    # the same float32 arithmetic in another order (dense over the cache
    # against the causal triangle): a few ulps
    np.testing.assert_allclose(np.asarray(pre), np.asarray(full[:, :S - 1]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(step[:, 0]),
                               np.asarray(full[:, -1]), rtol=1e-5, atol=1e-5)


def _dot_flops(jaxpr) -> int:
    """Multiply-add FLOPs of every dot_general in a jaxpr (2 a product)."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lc, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            total += 2 * int(np.prod(eqn.outvars[0].aval.shape)) \
                * int(np.prod([lhs[d] for d in lc]))
        for sub in _subjaxprs(eqn):
            total += _dot_flops(sub)
    return total


def _subjaxprs(eqn):
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else [v]):
            if hasattr(x, "jaxpr") and hasattr(x.jaxpr, "eqns"):
                yield x.jaxpr
            elif hasattr(x, "eqns"):
                yield x


@pytest.mark.parametrize("mode", ["prefill", "decode"])
def test_node_flops_equal_the_forward_s_dot_products(mode):
    """The priced equations against the layer that runs: the dsa node's
    FLOPs at the small size are the dot products of the block's jaxpr."""
    A, p, x, kw = _setup(S=32)
    arch = reduced(get_arch(DSV))          # the sizes ``_setup`` builds
    B, L = 2, 32
    if mode == "prefill":
        pos = jnp.broadcast_to(jnp.arange(L, dtype=jnp.int32), (B, L))
        fn = lambda x_: A.attend_dsa(x_, p, positions=pos, **kw)[0]
        arg = x
    else:
        cache = {"c_kv": jnp.zeros((B, L, 32)), "k_rope": jnp.zeros((B, L, 8)),
                 "index_keys": jnp.zeros((B, L, 16))}
        pos = jnp.full((B, 1), L - 1, jnp.int32)
        fn = lambda x_: A.attend_dsa(x_, p, positions=pos, cache=cache,
                                     cache_pos=jnp.int32(L - 1), **kw)[0]
        arg = x[:, :1]
    counted = _dot_flops(jax.make_jaxpr(fn)(arg).jaxpr)
    g = build_hdgraph(arch, ShapeSpec("t", L, B, mode))
    node = g.nodes[_dsa(g)[0]]
    assert counted == node.flops
