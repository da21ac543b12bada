"""Continuous-batching serving layer (ISSUE 7).

Acceptance contract:

- Engine.generate runs exactly ``s_prompt + n_new - 1`` model steps (the
  wasted trailing decode step is gone), with pinned AP ``n_graphs``;
  ``s_prompt == 0`` raises ValueError and ``n_new == 0`` returns [B, 0];
- coalesce_graphs merges same-program nodes across requests into
  block-aligned row-concatenated launches whose results AND per-block
  traced counters are bit-exact per request slice;
- the BatchServer serves >= 4 concurrent requests with tokens and APStats
  bit-identical to sequential single-request serving;
- admission control sheds load when the occupancy oracle says the bank is
  saturated; the IterableQueue drains under concurrent submitters.
"""
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import apc
from repro.core import ap


def _stats_equal(a: ap.APStats, b: ap.APStats) -> None:
    assert (a.sets, a.resets) == (b.sets, b.resets)
    assert (a.n_compare_cycles, a.n_write_cycles) == \
        (b.n_compare_cycles, b.n_write_cycles)
    assert np.array_equal(a.mismatch_hist, b.mismatch_hist)


def _tiny_ctx(n_arrays=4, rows=16, cols=96, x_levels=7):
    pool = apc.ArrayPool(n_arrays=n_arrays, rows=rows, cols=cols)
    return apc.APServeContext(apc.Runtime(pool), x_levels=x_levels)


def _tiny_engine(*, n_arrays=4, rows=64, temperature=0.0, max_len=10):
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import model as M
    from repro.models.quant import quantize_model_params
    from repro.serve.engine import Engine, ServeCfg
    base = get_smoke_config("qwen3-0.6b")
    cfg = base.with_(n_layers=1, d_model=16, d_ff=24, n_heads=2,
                     n_kv_heads=2, head_dim=8, vocab=32,
                     ternary=base.ternary.__class__(enabled=True))
    mesh = make_smoke_mesh()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    qparams = quantize_model_params(params)
    pool = apc.ArrayPool(n_arrays=n_arrays, rows=rows, cols=64)
    ctx = apc.APServeContext(apc.Runtime(pool), x_levels=7)
    return Engine(cfg, qparams, mesh,
                  ServeCfg(max_len=max_len, temperature=temperature),
                  ap_ctx=ctx)


# ---------------------------------------------------------------------------
# IterableQueue
# ---------------------------------------------------------------------------

def test_iterable_queue_fifo_and_close():
    from repro.serve.queue import ClosedQueue, IterableQueue
    q = IterableQueue()
    q.put(1)
    q.put(2)
    q.close()
    assert q.closed
    assert list(q) == [1, 2]
    with pytest.raises(ClosedQueue):
        q.put(3)
    with pytest.raises(ClosedQueue):
        q.close()


def test_iterable_queue_multiple_consumers_terminate():
    from repro.serve.queue import IterableQueue
    q = IterableQueue()
    got, lock = [], threading.Lock()

    def consume():
        for item in q:
            with lock:
                got.append(item)

    threads = [threading.Thread(target=consume) for _ in range(3)]
    for t in threads:
        t.start()
    for i in range(20):
        q.put(i)
    q.close()                       # ONE close stops all three consumers
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert sorted(got) == list(range(20))


def test_iterable_queue_concurrent_submitters_drain():
    from repro.serve.queue import IterableQueue
    q = IterableQueue(maxsize=4)    # bounded: producers block when ahead
    n_producers, per = 5, 8
    barrier = threading.Barrier(n_producers)

    def produce(base):
        barrier.wait()              # maximize interleaving
        for i in range(per):
            q.put(base + i)

    threads = [threading.Thread(target=produce, args=(100 * p,))
               for p in range(n_producers)]
    for t in threads:
        t.start()
    got = []
    while len(got) < n_producers * per:
        got.append(q.get())
    for t in threads:
        t.join(timeout=30)
    q.close()
    assert list(q) == []
    assert sorted(got) == sorted(100 * p + i for p in range(n_producers)
                                 for i in range(per))


# ---------------------------------------------------------------------------
# coalesce_graphs: block-aligned row concatenation
# ---------------------------------------------------------------------------

def _mac_graph(ctx, lin, seed, t=3):
    from repro.apc.graph import ProgramGraph
    rng = np.random.default_rng(seed)
    g = ProgramGraph()
    x_int = jnp.asarray(rng.integers(-7, 8, size=(t, lin.kp)), jnp.int32)
    call = lin.add_call(g, x_int, max_cols=ctx.max_cols, max_q=7)
    return g, call


def test_coalesce_merges_and_slices_bit_exact():
    from repro.apc.graph import MergedGraphView, coalesce_graphs
    from repro.apc.layers import APLinear
    ctx = _tiny_ctx()
    rng = np.random.default_rng(0)
    lin = APLinear.from_dense(rng.normal(size=(8, 4)))
    graphs, calls = zip(*[_mac_graph(ctx, lin, seed, t=2 + seed)
                          for seed in range(3)])
    merged, maps = coalesce_graphs(list(graphs),
                                   block_rows=ctx.runtime.pool.rows)
    # same-program same-level nodes fold: fewer merged nodes than sources
    assert len(merged) < sum(len(g) for g in graphs)
    res = ctx.runtime.run_graph(merged, collect_stats=True)
    for g, call, m in zip(graphs, calls, maps):
        solo_stats = ap.APStats(radix=3)
        solo = ctx.runtime.run_graph(g, stats=solo_stats)
        view = MergedGraphView(res, m, solo.report)
        # result slice == standalone run, node by node
        for nid in range(len(g)):
            assert np.array_equal(np.asarray(view[nid]),
                                  np.asarray(solo[nid]))
        # per-block counters partition exactly: slicing the merged node's
        # TracedStats by this request's block range reproduces its solo
        # APStats bit-for-bit
        from repro.apc.stats import TracedStats, accumulate
        sliced_stats = ap.APStats(radix=3)
        for nid, node in enumerate(g.nodes):
            sl = m[nid]
            tr = res.traced[sl.node]
            accumulate(sliced_stats,
                       TracedStats(tr.block_counts[sl.block_lo:sl.block_hi]),
                       node.compiled, n_rows=node.rows)
        _stats_equal(sliced_stats, solo_stats)


def test_coalesce_rejects_already_merged_nodes():
    from repro.apc.graph import coalesce_graphs
    from repro.apc.layers import APLinear
    ctx = _tiny_ctx()
    lin = APLinear.from_dense(np.random.default_rng(1).normal(size=(8, 4)))
    g1, _ = _mac_graph(ctx, lin, 0)
    g2, _ = _mac_graph(ctx, lin, 1)
    merged, _ = coalesce_graphs([g1, g2], block_rows=ctx.runtime.pool.rows)
    assert any(n.block_valid is not None for n in merged.nodes)
    with pytest.raises(ValueError):
        coalesce_graphs([merged], block_rows=ctx.runtime.pool.rows)


def test_pool_run_block_valid_masks_interior_padding():
    """A row-concatenated launch (two segments padded to block multiples)
    produces the same valid-row outputs and counters as two standalone
    launches of the segments."""
    from repro.apc.mac import (compile_mac_tiled, encode_mac_rows_jnp,
                               mac_acc_width)
    pool = apc.ArrayPool(n_arrays=2, rows=8, cols=96)
    rng = np.random.default_rng(3)
    radix, K, max_q = 3, 6, 7
    width = mac_acc_width(radix, K, max_q)
    tiled = compile_mac_tiled(radix, K, width, K, max_cols=96)
    compiled = tiled.programs[0]

    def encode(rows_n, seed):
        x = rng.integers(-max_q, max_q + 1, (rows_n, K))
        w = np.random.default_rng(seed).integers(-1, 2, (rows_n, K))
        return encode_mac_rows_jnp(jnp.asarray(x), jnp.asarray(w),
                                   radix, width)

    a = encode(5, 1)      # 5 valid rows -> one block of 8
    b = encode(11, 2)     # 11 valid rows -> two blocks of 8
    pad_a = jnp.pad(a, ((0, 8 - 5), (0, 0)))
    pad_b = jnp.pad(b, ((0, 16 - 11), (0, 0)))
    cat = jnp.concatenate([pad_a, pad_b], axis=0)
    out, tr = pool.run(cat, compiled, collect_stats=True,
                       block_valid=(5, 8, 3))
    out_a, tr_a = pool.run(a, compiled, collect_stats=True)
    out_b, tr_b = pool.run(b, compiled, collect_stats=True)
    assert np.array_equal(np.asarray(out[:5]), np.asarray(out_a))
    assert np.array_equal(np.asarray(out[5:16]), np.asarray(out_b))
    cat_counts = np.asarray(tr.block_counts)
    assert np.array_equal(cat_counts[:1], np.asarray(tr_a.block_counts))
    assert np.array_equal(cat_counts[1:], np.asarray(tr_b.block_counts))


def test_pool_run_block_valid_validates():
    pool = apc.ArrayPool(n_arrays=2, rows=8, cols=96)
    from repro.apc.mac import compile_mac_tiled
    tiled = compile_mac_tiled(3, 6, 7, 6, max_cols=96)
    compiled = tiled.programs[0]
    arr = jnp.zeros((12, compiled.min_cols), jnp.int8)  # not block multiple
    with pytest.raises(ValueError):
        pool.run(arr, compiled, block_valid=(8, 4))
    arr = jnp.zeros((16, compiled.min_cols), jnp.int8)
    with pytest.raises(ValueError):
        pool.run(arr, compiled, block_valid=(8,))      # wrong count
    with pytest.raises(ValueError):
        pool.run(arr, compiled, block_valid=(8, 9))    # > rows


# ---------------------------------------------------------------------------
# Engine.generate: fixed step count + edge cases
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_generate_step_count_and_n_graphs_regression():
    """The j = n_new-1 decode step used to run and get discarded; pinned:
    exactly s_prompt + n_new - 1 model steps, and on the AP path exactly
    2 graphs per layer per step."""
    eng = _tiny_engine()
    calls = {"n": 0}
    orig = eng._step

    def counting_step(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    eng._step = counting_step
    s_prompt, n_new = 3, 4
    toks = eng.generate(np.array([[3, 5, 7]], dtype=np.int32), n_new)
    assert toks.shape == (1, n_new)
    expect_steps = s_prompt + n_new - 1
    assert calls["n"] == expect_steps
    assert eng.last_latency["n_model_steps"] == expect_steps
    assert eng.last_latency["n_prefill_steps"] == s_prompt
    assert eng.last_latency["n_decode_steps"] == n_new - 1
    # 1 ternary MLP layer => 2 graphs (gate+up, down) per model step
    assert eng.ap_ctx.n_graphs == 2 * expect_steps


@pytest.mark.slow
def test_generate_empty_prompt_raises_and_n_new_zero_empty():
    eng = _tiny_engine()
    with pytest.raises(ValueError, match="empty prompt"):
        eng.generate(np.zeros((1, 0), dtype=np.int32), 3)
    out = eng.generate(np.array([[3, 5]], dtype=np.int32), 0)
    assert out.shape == (1, 0) and out.dtype == np.int32
    assert eng.last_latency["n_model_steps"] == 0
    lat = eng.last_latency
    assert abs(lat["prefill_ms"] + lat["decode_ms"] + lat["other_ms"]
               - lat["request_ms"]) < 1e-6


def test_request_validates_without_model_run():
    """new_request validation does not need a forward pass."""
    from repro.serve.engine import Engine, ServeCfg

    class _Cfg:
        enc_layers = 0
    eng = Engine.__new__(Engine)
    eng.cfg = _Cfg()
    eng.serve = ServeCfg(max_len=8)
    with pytest.raises(ValueError, match="empty prompt"):
        eng.new_request(np.zeros((1, 0), dtype=np.int32), 2)
    with pytest.raises(ValueError, match="n_new"):
        eng.new_request(np.array([[1]], dtype=np.int32), -1)


# ---------------------------------------------------------------------------
# BatchServer: bit-exact continuous batching + admission + drain
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_batched_serving_bit_identical_to_sequential():
    """>= 4 concurrent requests through the BatchServer return the same
    tokens AND the same per-request APStats as sequential Engine.generate
    single-request serving."""
    from repro.serve.batcher import AdmissionCfg, BatchServer
    prompts = [np.array([[1 + i, 2 + i, 3 + i]], dtype=np.int32)
               for i in range(4)]
    n_new = 3

    eng_seq = _tiny_engine()
    seq = []
    for p in prompts:
        toks = eng_seq.generate(p, n_new)
        seq.append((toks, eng_seq.ap_report()))

    eng = _tiny_engine()
    with BatchServer(eng, admission=AdmissionCfg(max_inflight=8)) as srv:
        handles = [srv.submit(p, n_new) for p in prompts]
        results = [(h.result(timeout=300), h.ap_report()) for h in handles]
    assert srv.n_waves > 0
    for (bt, br), (st, sr) in zip(results, seq):
        assert np.array_equal(bt, st)
        for key in ("sets", "resets", "compare_cycles", "write_cycles",
                    "energy_total_j", "n_graphs", "n_programs",
                    "makespan_cycles", "sequential_cycles",
                    "makespan_ns", "sequential_ns"):
            assert br[key] == sr[key], key


def test_merged_wave_spans_on_the_profiler_clock(profiled):
    """A wave that merges two requests shows on the profiler's clock: the
    dispatcher's wave around both request threads' model steps (graph
    builds inside) and rendezvous, and the leader's merge around the
    graph run and its pool launches."""
    from repro.serve.batcher import AdmissionCfg, BatchServer

    def nest(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    eng = _tiny_engine()
    with BatchServer(eng, admission=AdmissionCfg(max_inflight=2)) as srv:
        def two():
            hs = [srv.submit(np.array([[1 + i, 2 + i]], np.int32), 1)
                  for i in range(2)]
            return [h.result(timeout=300) for h in hs]

        two()                                   # compiles every shape
        _, spans = profiled(two)
    evs = [(n, s, e, t) for t, line in spans.items() for n, s, e in line]

    def named(name):
        return [e for e in evs if e[0] == name]

    merged = [w for w in named("ap.serve.wave")
              if len({r[3] for r in named("ap.serve.rendezvous")
                      if nest(r, w)}) == 2]
    assert merged
    wave = merged[0]
    inside = [e for e in evs if nest(e, wave) and e is not wave]
    steps = [e for e in inside if e[0] == "ap.model.step"]
    assert len({e[3] for e in steps}) == 2
    for name in ("ap.model.graph_build", "ap.serve.rendezvous"):
        assert all(any(nest(e, st) for st in steps if st[3] == e[3])
                   for e in inside if e[0] == name)
    merge = [e for e in inside if e[0] == "ap.serve.merge"]
    runs = [e for e in inside if e[0] == "ap.runtime.run_graph"]
    assert merge and all(any(nest(r, m) and r[3] == m[3] for m in merge)
                         for r in runs)
    launches = [e for e in inside if e[0] == "ap.pool.launch"]
    assert launches and all(any(nest(e, r) for r in runs) for e in launches)
    # the spans of each thread nest: two are disjoint or one holds the other
    for line in spans.values():
        for i, a in enumerate(line):
            for b in line[i + 1:]:
                assert (a[2] <= b[1] or b[2] <= a[1] or nest(a, b)
                        or nest(b, a)), (a, b)


@pytest.mark.slow
def test_batched_serving_unequal_lengths_and_late_join():
    """Continuous batching: requests of different prompt/decode lengths
    join and retire mid-stream, still bit-exact vs sequential."""
    from repro.serve.batcher import AdmissionCfg, BatchServer
    specs = [(np.array([[1, 2, 3]], dtype=np.int32), 4),
             (np.array([[4, 5]], dtype=np.int32), 2),
             (np.array([[6]], dtype=np.int32), 5),
             (np.array([[7, 8, 9]], dtype=np.int32), 1),
             (np.array([[2, 4]], dtype=np.int32), 0)]

    eng_seq = _tiny_engine()
    seq = [eng_seq.generate(p, n) for p, n in specs]

    eng = _tiny_engine()
    with BatchServer(eng, admission=AdmissionCfg(max_inflight=3)) as srv:
        handles = [srv.submit(p, n) for p, n in specs]
        out = [h.result(timeout=300) for h in handles]
    for got, want in zip(out, seq):
        assert np.array_equal(got, want)


def test_admission_cfg_validates():
    from repro.serve.batcher import AdmissionCfg
    with pytest.raises(ValueError):
        AdmissionCfg(policy="drop")
    with pytest.raises(ValueError):
        AdmissionCfg(max_inflight=0)


def test_wave_cost_cycles_scales_with_requests():
    from repro.apc.mac import compile_mac_tiled
    from repro.serve.batcher import wave_cost_cycles
    tiled = compile_mac_tiled(3, 6, 7, 6, max_cols=96)
    compiled = tiled.programs[0]
    prof = [[(compiled, 8, ())]]           # one 8-row node per step
    one = wave_cost_cycles([prof], n_arrays=1, rows_per_array=8)
    four = wave_cost_cycles([prof] * 4, n_arrays=1, rows_per_array=8)
    assert one > 0
    assert four > one                      # saturated bank: cost stacks
    assert wave_cost_cycles([], n_arrays=1, rows_per_array=8) == 0


@pytest.mark.slow
def test_admission_rejects_under_saturated_bank():
    """With a max_wave_cycles below the cost of stacking another request
    onto a busy 1-array bank, policy='reject' sheds the excess request
    while the admitted ones complete."""
    from repro.serve.batcher import (AdmissionCfg, AdmissionRejected,
                                     BatchServer)
    eng = _tiny_engine(n_arrays=1, rows=16)
    # price one request's wave on the saturated bank, then forbid two
    with BatchServer(eng, admission=AdmissionCfg(max_inflight=4)) as probe:
        h = probe.submit(np.array([[1, 2, 3]], dtype=np.int32), 3)
        h.result(timeout=300)
        one_req = probe._last_profile
    assert one_req is not None
    from repro.serve.batcher import wave_cost_cycles
    pool = eng.ap_ctx.runtime.pool
    one_cost = wave_cost_cycles([one_req], n_arrays=pool.n_arrays,
                                rows_per_array=pool.rows)

    eng2 = _tiny_engine(n_arrays=1, rows=16)
    adm = AdmissionCfg(max_inflight=4, max_wave_cycles=int(one_cost * 1.5),
                       policy="reject")
    with BatchServer(eng2, admission=adm) as srv:
        first = srv.submit(np.array([[1, 2, 3]], dtype=np.int32), 6)
        first.result(timeout=300)          # primes the profile oracle
        a = srv.submit(np.array([[1, 2, 3]], dtype=np.int32), 6)
        b = srv.submit(np.array([[4, 5, 6]], dtype=np.int32), 6)
        outcomes = []
        for h in (a, b):
            try:
                h.result(timeout=300)
                outcomes.append("served")
            except AdmissionRejected:
                outcomes.append("rejected")
    assert "rejected" in outcomes          # the bank shed load
    assert "served" in outcomes            # but kept serving


@pytest.mark.slow
def test_batch_server_queue_drain_under_concurrent_submitters():
    """Many threads submitting concurrently: every request completes and
    close() drains the backlog."""
    from repro.serve.batcher import AdmissionCfg, BatchServer
    eng = _tiny_engine()
    handles, lock = [], threading.Lock()
    srv = BatchServer(eng, admission=AdmissionCfg(max_inflight=4))

    def client(seed):
        h = srv.submit(np.array([[1 + seed, 2 + seed]], dtype=np.int32), 2)
        with lock:
            handles.append(h)

    threads = [threading.Thread(target=client, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    srv.close(wait=True)
    assert len(handles) == 6
    for h in handles:
        toks = h.result(timeout=10)        # already done after close()
        assert toks.shape == (1, 2)
    with pytest.raises(RuntimeError, match="closed"):
        srv.submit(np.array([[1, 2]], dtype=np.int32), 1)


@pytest.mark.slow
def test_batch_server_fails_bad_request_only():
    """An invalid request fails its own handle; neighbors are served."""
    from repro.serve.batcher import AdmissionCfg, BatchServer
    eng = _tiny_engine()
    with BatchServer(eng, admission=AdmissionCfg(max_inflight=4)) as srv:
        good = srv.submit(np.array([[1, 2]], dtype=np.int32), 2)
        bad = srv.submit(np.zeros((1, 0), dtype=np.int32), 2)
        assert good.result(timeout=300).shape == (1, 2)
        with pytest.raises(ValueError, match="empty prompt"):
            bad.result(timeout=300)
