"""AP-backed model layers: ternary projections through the graph runtime.

The serving story of the paper's AP: every ternary projection of a model
(`models/mlp.py` SwiGLU, `models/moe.py` experts) is a ternary matmul, every
ternary matmul is a K-tiled MAC program, and *independent* projections — the
gate and up projections of one MLP, the experts of one MoE layer — are
independent subgraphs of ONE :class:`~repro.apc.graph.ProgramGraph`, so the
runtime interleaves their tile programs across the array bank instead of
draining them one by one.

- :class:`APLinear` — one projection ``y = (x @ w_ter) * w_scale`` with a
  per-(radix, K, width, k_tile) compiled-program cache
  (:func:`~repro.apc.mac.compile_mac_tiled` is lru-cached; every request
  replays the same TiledMac).
- :class:`APServeContext` — per-request aggregation: one
  :class:`~repro.core.ap.APStats` across every AP-served projection, graph
  makespan/sequential totals from the occupancy model, and a Table XI
  energy report.  Activations quantize to a signed integer grid
  (``x_levels``) per call — the AP computes exact integer dot products on
  the quantized activations; fidelity is the quantization's, exactness the
  AP's.
- :class:`APExpertStack` — the held experts of one MoE projection as ONE
  resident digit plane and one schedule: a grouped MAC whose rows belong
  to many experts.  A dense MLP projection is served as a stack of one
  (``models/mlp.py``), converted once and kept by the context under its
  layer and projection (:meth:`APServeContext.stack`).
- :func:`ap_moe_dispatch` — sort (token, expert) pairs on the host and run
  every held pair's gate and up projections as one grouped MAC each, then
  the down projections as a third (the multi-array occupancy workload of
  the AP-tutorial framing).
- :func:`ap_serving` — context manager the serve engine uses to flip
  ``models.mlp.mlp`` / ``models.moe.moe_ffn`` onto the AP path without
  threading a runtime handle through the whole model stack.
"""
from __future__ import annotations

import contextvars
import functools
from contextlib import contextmanager
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ap import APStats
from ..core.energy import energy_from_stats
from ..kernels.ternary_matmul.ref import quantize_ternary, unpack_ternary
from . import trace
from .caches import ResidentHandle, ResidentStore
from .graph import ProgramGraph
from .mac import (W_MINUS, W_PLUS, W_ZERO, compile_mac_tiled,
                  decode_signed_digits_jnp, encode_weight_digits_jnp,
                  mac_acc_width, mac_weight_support, matmul_mac_rows,
                  weight_digest)
from .metrics import get_registry
from .power import PowerAccum, graph_power
from .runtime import Runtime

__all__ = ["APExpertStack", "APLinear", "APServeContext", "APSink",
           "ap_layer", "ap_moe_dispatch", "ap_serving", "ap_request_scope",
           "current_ap_context", "current_ap_layer", "pair_slots",
           "N_MASKED_MAC"]

# compare-key mask width of the MAC sweeps: 3 LUT columns + 1 weight
# predicate column (what the Table XI matchline model charges per compare)
N_MASKED_MAC = 4


class APCall(NamedTuple):
    """Handle to one projection added to a graph: decode after the run."""
    node: int
    radix: int
    t: int
    n: int
    w_scale: jax.Array

    def decode(self, results, x_scale) -> jax.Array:
        acc = decode_signed_digits_jnp(results[self.node], self.radix)
        y = acc.reshape(self.t, self.n).astype(jnp.float32)
        return y * jnp.asarray(x_scale, jnp.float32) \
            * self.w_scale[None, :]


class APLinear:
    """One ternary projection served by the AP runtime.

    ``w_ter`` [K, N] in {-1, 0, +1}, ``w_scale`` [N] float (absmean
    per-channel scale, as produced by :func:`quantize_ternary`).

    ``sparse`` (default on) compiles the projection's MAC against the
    weights' per-k digit support (:func:`~repro.apc.mac.
    mac_weight_support`), pruning every add/sub sweep whose predicate
    digit never occurs — bit-exact by construction, since the pruned
    sweeps could not have matched any of this projection's rows.

    ``store`` (weight-stationary dataflow): a
    :class:`~repro.apc.caches.ResidentStore` to pin the weight digit
    plane into at construction; every subsequent call slices the
    resident plane instead of re-encoding/re-uploading weight columns
    (:meth:`pin` attaches a store later — ``__call__`` auto-pins into
    the serving context's pool store).
    """

    def __init__(self, w_ter: jax.Array, w_scale: jax.Array, *,
                 radix: int = 3, label: str = "",
                 store: ResidentStore | None = None, sparse: bool = True):
        self.w_ter = jnp.asarray(w_ter, jnp.int8)
        self.w_scale = jnp.asarray(w_scale, jnp.float32)
        self.kp, self.n = self.w_ter.shape
        self.radix = radix
        self.label = label
        self.sparse = sparse
        wT = np.asarray(self.w_ter).T                  # [N, K'] row plane
        self._support = mac_weight_support(wT)
        self._digest = weight_digest(wT)
        self._n_zero = int((wT == 0).sum())
        self._n_weights = int(wT.size)
        self._res_key = f"lin:{label}" if label else f"lin:{self._digest}"
        self._store: ResidentStore | None = None
        self._handle: ResidentHandle | None = None
        if store is not None:
            self.pin(store)

    def _plane_fn(self) -> jax.Array:
        # the ONE weight-side encode of the weight-stationary dataflow:
        # runs on a pin miss only (bumps the mac.weight_encodes counter)
        return encode_weight_digits_jnp(self.w_ter.T)

    def pin(self, store: ResidentStore) -> ResidentHandle:
        """Write this projection's weight digit plane into ``store``
        (content-keyed get-or-put) and serve subsequent calls from it."""
        self._store = store
        self._handle = store.pin(self._res_key, self._digest,
                                 self._plane_fn)
        return self._handle

    @property
    def weight_sparsity(self) -> float:
        """Measured zero fraction of the ternary weights."""
        return self._n_zero / max(1, self._n_weights)

    @classmethod
    def from_packed(cls, packed: jax.Array, scale: jax.Array,
                    **kw) -> "APLinear":
        """From the 16-per-int32 packed serving weights."""
        return cls(unpack_ternary(packed, dtype=jnp.int8), scale, **kw)

    @classmethod
    def from_dense(cls, w: jax.Array, **kw) -> "APLinear":
        """Quantize a dense float matrix to balanced ternary + scale."""
        w_ter, scale = quantize_ternary(jnp.asarray(w, jnp.float32))
        return cls(w_ter, scale, **kw)

    def __repr__(self) -> str:
        return (f"APLinear({self.kp}x{self.n}, radix={self.radix}"
                f"{', ' + self.label if self.label else ''})")

    def add_call(self, graph: ProgramGraph, x_int: jax.Array, *,
                 max_cols: int, max_q: int, k_tile: int | None = None
                 ) -> APCall:
        """Add this projection on ``x_int`` [T, K] (|x| <= max_q) to the
        graph as a K-tiled MAC over all T*N output rows; returns the
        decode handle."""
        from ..kernels.ternary_matmul.ap import default_k_tile
        t, k = x_int.shape
        if k > self.kp:
            raise ValueError(f"x has K={k}, projection K'={self.kp}")
        if k < self.kp:                   # pack-time padding rows: w == 0
            x_int = jnp.pad(x_int, ((0, 0), (0, self.kp - k)))
        width = mac_acc_width(self.radix, self.kp, max_q)
        kt = k_tile if k_tile is not None else default_k_tile(max_cols,
                                                              width)
        tiled = compile_mac_tiled(
            self.radix, self.kp, width, min(kt, self.kp), max_cols=max_cols,
            support=self._support if self.sparse else None)
        resident = None
        if self._store is not None:
            # re-pin (get-or-put): a hit returns the live handle with zero
            # encode work, an eviction transparently re-encodes once
            prev = self._handle
            resident = self._store.pin(self._res_key, self._digest,
                                       self._plane_fn)
            self._handle = resident
            graph.bump("resident_hits" if resident is prev
                       else "resident_misses", 1)
        else:
            graph.bump("resident_misses", 1)
        graph.bump("weight_zeros", self._n_zero)
        graph.bump("weight_digits", self._n_weights)
        if resident is None:
            x_rows, w_rows = matmul_mac_rows(x_int, self.w_ter)  # [T*N, K']
        else:
            # weight rows come from the resident plane (same matmul_mac_rows
            # ordering: row t*N + n holds w_ter.T[n]) — never materialized
            x_rows, w_rows = jnp.repeat(x_int, self.n, axis=0), None
        node = graph.add_mac_tiled(x_rows, w_rows, tiled,
                                   label=f"{self.label}:" if self.label
                                   else "", resident=resident,
                                   charge_upload=True)
        return APCall(node, self.radix, t, self.n, self.w_scale)

    def __call__(self, x: jax.Array, ctx: "APServeContext") -> jax.Array:
        """Standalone projection: quantize, one-node graph, run, decode.

        Auto-pins the weights into the context pool's resident store on
        first use, so repeat calls are weight-stationary."""
        if self._store is None:
            store = getattr(ctx.runtime.pool, "resident", None)
            if store is not None:
                self.pin(store)
        graph = ProgramGraph()
        x_int, s = ctx.quantize(x)
        call = self.add_call(graph, x_int, max_cols=ctx.max_cols,
                             max_q=ctx.x_levels)
        res = ctx.run_graph(graph)
        return call.decode(res, s).astype(x.dtype)


class APGroupedCall(NamedTuple):
    """Handle to one grouped MAC added to a graph: decode after the run."""
    node: int
    radix: int
    groups: jax.Array              # [P]: each row's projection
    w_scale: jax.Array             # [G, N]: every projection's scale

    def decode(self, results, x_scale) -> jax.Array:
        """[P, N] float32; ``x_scale`` [P] (or [P, 1]) per input row, or
        one scale for every row."""
        return _decode_rows(results[self.node], jnp.asarray(x_scale),
                            self.w_scale, self.groups, radix=self.radix)


@functools.partial(jax.jit, static_argnames=("radix",))
def _decode_rows(digits, x_scale, w_scale, groups, *, radix: int
                 ) -> jax.Array:
    """Accumulator digits [P * N, width] -> [P, N] float32, scaled per
    input row (or by one scale) and by each row's projection's output
    scales (one compiled call per shape)."""
    p, n = groups.shape[0], w_scale.shape[1]
    acc = decode_signed_digits_jnp(digits, radix)
    y = acc.reshape(p, n).astype(jnp.float32)
    return y * x_scale.astype(jnp.float32).reshape(-1, 1) * w_scale[groups]


@functools.partial(jax.jit, static_argnames=("levels",))
def quantize_rows_jnp(x, *, levels: int) -> tuple[jax.Array, jax.Array]:
    """x float [T, K] -> (x_int int32 [T, K], scale [T]): each row on its
    own grid, ``|x_int| <= levels``."""
    xf = jnp.asarray(x, jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / levels, 1e-8)
    xi = jnp.clip(jnp.round(xf / s[:, None]), -levels,
                  levels).astype(jnp.int32)
    return xi, s


class APExpertStack:
    """The experts of one MoE projection (or one dense projection, a
    stack of one), served as ONE grouped MAC.

    ``w_ter`` [E, K, N] in {-1, 0, +1}, ``w_scale`` [E, N] (absmean per
    output channel of each expert).  The experts' weights stack into one
    resident digit plane ``[E * N, K]`` (expert ``e``'s output ``n`` is
    plane row ``e * N + n``), pinned into ``store`` once; one K-tiled
    schedule, compiled against the union of every expert's digit support,
    serves them all.  :meth:`add_call` puts the rows of every (input row,
    expert) pair of a call into that one MAC, each row reading its own
    expert's slice of the plane — so a call costs one MAC's launches
    whichever and however many experts it hits.
    """

    def __init__(self, w_ter: jax.Array, w_scale: jax.Array, *,
                 radix: int = 3, label: str = "",
                 store: ResidentStore | None = None):
        w_ter = jnp.asarray(w_ter, jnp.int8)
        self.e, self.k, self.n = w_ter.shape
        self.w_scale = jnp.asarray(w_scale, jnp.float32).reshape(self.e,
                                                                 self.n)
        self.radix = radix
        self.label = label
        rows = jnp.swapaxes(w_ter, 1, 2).reshape(self.e * self.n, self.k)
        # the one weight-side encode; the support masks (bit v: digit v
        # occurs at k in some expert, as mac_weight_support) on the device
        self.plane = encode_weight_digits_jnp(rows)
        masks = sum(jnp.any(self.plane == v, axis=0).astype(jnp.int32) << v
                    for v in (W_MINUS, W_ZERO, W_PLUS))
        self._support = tuple(int(m) for m in np.asarray(masks))
        self._digest = weight_digest(np.asarray(rows))
        zeros = jnp.sum(w_ter == 0, axis=(1, 2))
        self._n_zero = [int(z) for z in np.asarray(zeros)]
        self._res_key = f"lin:{label}" if label else f"lin:{self._digest}"
        self._store = store if store is not None else \
            ResidentStore(maxsize=1, name=self._res_key)
        self._handle = self._store.pin(self._res_key, self._digest,
                                       lambda: self.plane)

    @classmethod
    def from_dense(cls, w_stack: jax.Array, **kw) -> "APExpertStack":
        """Ternarize each expert of a float stack [E, K, N] (absmean per
        output channel, :func:`quantize_ternary`), in float32."""
        w_ter, scale = jax.vmap(quantize_ternary)(
            jnp.asarray(w_stack, jnp.float32))
        return cls(w_ter, scale, **kw)

    @classmethod
    def from_packed(cls, packed: jax.Array, scale: jax.Array,
                    **kw) -> "APExpertStack":
        """A stack of one projection, from its 16-per-int32 packed serving
        weights ``[K'/16, N]`` and scale ``[N]`` (the digits as served)."""
        return cls(unpack_ternary(packed, dtype=jnp.int8)[None],
                   jnp.asarray(scale)[None], **kw)

    @classmethod
    def from_linears(cls, lins: list["APLinear"], **kw) -> "APExpertStack":
        """Stack per-expert projections of one shape."""
        return cls(jnp.stack([lin.w_ter for lin in lins]),
                   jnp.stack([lin.w_scale for lin in lins]),
                   radix=lins[0].radix, **kw)

    def __repr__(self) -> str:
        return (f"APExpertStack({self.e}x{self.k}x{self.n}, "
                f"radix={self.radix}{', ' + self.label if self.label else ''})")

    def tiled(self, max_cols: int, max_q: int, k_tile: int | None = None):
        """The one schedule of every call: the K-tiled MAC at the width
        that inputs ``|x| <= max_q`` need, pruned against the union of the
        experts' digit support."""
        from ..kernels.ternary_matmul.ap import default_k_tile
        width = mac_acc_width(self.radix, self.k, max_q)
        kt = k_tile if k_tile is not None else default_k_tile(max_cols,
                                                              width)
        return compile_mac_tiled(self.radix, self.k, width, min(kt, self.k),
                                 max_cols=max_cols, support=self._support)

    def add_call(self, graph: ProgramGraph, x_int: jax.Array,
                 experts: np.ndarray, *, max_cols: int, max_q: int,
                 k_tile: int | None = None) -> APGroupedCall:
        """Add rows ``x_int`` [P, K] (|x| <= max_q), row ``p`` through
        expert ``experts[p]`` (host ints), to the graph as one K-tiled MAC
        over all P * N output rows; returns the decode handle."""
        k = x_int.shape[1]
        if k != self.k:
            raise ValueError(f"x has K={k}, experts have K={self.k}")
        tiled = self.tiled(max_cols, max_q, k_tile)
        prev = self._handle
        self._handle = self._store.pin(self._res_key, self._digest,
                                       lambda: self.plane)
        graph.bump("resident_hits" if self._handle is prev
                   else "resident_misses", 1)
        hit = sorted(set(int(e) for e in experts))
        graph.bump("weight_zeros", sum(self._n_zero[e] for e in hit))
        graph.bump("weight_digits", len(hit) * self.k * self.n)
        groups = jnp.asarray(np.asarray(experts, np.int32))
        node = graph.add_grouped_mac(
            x_int, groups, tiled, self._handle, n_out=self.n,
            label=f"{self.label}:" if self.label else "",
            charge_upload=True)
        return APGroupedCall(node, self.radix, groups, self.w_scale)


class APSink:
    """Per-request aggregation target: one :class:`APStats` plus the
    occupancy-model totals (makespan/sequential cycles and ns) and graph
    counts a request accumulates across its AP-served projections.

    A sequential :class:`APServeContext` owns one default sink; the
    continuous-batching path (``serve/batcher.py``) gives every in-flight
    request its own sink via :func:`ap_request_scope`, so many requests can
    share one context (and one merged graph run) while keeping bit-exact
    per-request accounting.
    """

    # builder-side meta counters folded from ProgramGraph.meta: sparsity
    # pruning totals + resident-bank hit tracking + measured weight zeros
    META_KEYS = ("pruned_write_cycles", "pruned_compare_cycles",
                 "emitted_passes", "pruned_passes",
                 "resident_hits", "resident_misses",
                 "weight_zeros", "weight_digits")

    def __init__(self, radix: int = 3):
        self.radix = radix
        self.reset()

    def reset(self) -> None:
        self.stats = APStats(radix=self.radix)
        self.makespan_cycles = 0
        self.sequential_cycles = 0
        self.makespan_ns = 0.0
        self.sequential_ns = 0.0
        self.n_graphs = 0
        self.n_programs = 0
        for k in self.META_KEYS:
            setattr(self, k, 0)
        # per-request power rollup: per-array Table XI energy + busy time
        # + peak W, folded from every graph run's (schedule, counters) join
        self.power = PowerAccum(radix=self.radix, n_masked=N_MASKED_MAC)
        # deferred counter attributions: (traced, compiled, n_rows, label).
        # The batcher defers the device->host counter sync so the host can
        # encode wave k+1 while wave k's launches drain; flush() settles
        # them into ``stats`` (report() flushes implicitly).
        self._deferred: list[tuple] = []
        # deferred power joins: (schedule, traced_map, labels, n_arrays) —
        # same deferred-sync contract as ``_deferred``
        self._deferred_power: list[tuple] = []

    def defer(self, traced, compiled, n_rows: int, label: str = "") -> None:
        """Queue one traced-counter attribution without syncing the device."""
        self._deferred.append((traced, compiled, n_rows, label))

    def defer_power(self, schedule: list, traced: dict, labels: dict,
                    n_arrays_local: int) -> None:
        """Queue one graph run's power join (schedule intervals + per-node
        counters) without syncing the device."""
        self._deferred_power.append((schedule, traced, labels,
                                     n_arrays_local))

    def flush(self) -> None:
        """Settle deferred attributions into ``stats`` (host sync)."""
        from .stats import accumulate
        pend, self._deferred = self._deferred, []
        for traced, compiled, n_rows, label in pend:
            accumulate(self.stats, traced, compiled, n_rows, label=label)
        pend_p, self._deferred_power = self._deferred_power, []
        for schedule, traced, labels, nal in pend_p:
            self.power.add(graph_power(
                schedule, traced, radix=self.radix, n_masked=N_MASKED_MAC,
                n_arrays_local=nal, labels=labels))

    # everything a merged serve WAVE can mutate: the occupancy scalars +
    # meta counters (add_report/add_meta) and the deferred lists (defer/
    # defer_power).  stats and power only move at flush(), which the
    # batcher never calls mid-wave — so a scalar snapshot + list lengths
    # is a complete wave-granular checkpoint.
    _WAVE_SCALARS = ("makespan_cycles", "sequential_cycles", "makespan_ns",
                     "sequential_ns", "n_graphs", "n_programs") + META_KEYS

    def checkpoint(self) -> tuple:
        """Snapshot the wave-mutable state (see ``_WAVE_SCALARS``): the
        batcher takes one before each merged wave so an aborted sibling
        can roll back and re-run solo without double-charging."""
        scalars = {k: getattr(self, k) for k in self._WAVE_SCALARS}
        return (scalars, len(self._deferred), len(self._deferred_power))

    def restore(self, ck: tuple) -> None:
        """Roll back to a :meth:`checkpoint` (scalars reset, deferred
        lists truncated to their checkpointed lengths)."""
        scalars, n_def, n_pow = ck
        for k, v in scalars.items():
            setattr(self, k, v)
        del self._deferred[n_def:]
        del self._deferred_power[n_pow:]

    def add_report(self, report: dict) -> None:
        """Fold one graph run's occupancy report into the totals."""
        self.makespan_cycles += report["makespan_cycles"]
        self.sequential_cycles += report["sequential_cycles"]
        self.makespan_ns += report["makespan_ns"]
        self.sequential_ns += report["sequential_ns"]
        self.n_graphs += 1
        self.n_programs += report["n_nodes"]

    def add_meta(self, meta: dict) -> None:
        """Fold one graph's builder-side meta (sparsity + residency)."""
        for k in self.META_KEYS:
            setattr(self, k, getattr(self, k) + meta.get(k, 0))

    def report(self, n_masked: int = N_MASKED_MAC) -> dict:
        """Aggregated per-request accounting: functional-simulator counters
        + Table XI energy + graph-scheduler occupancy + sparsity/residency
        attribution (pruned vs emitted passes, resident-bank hit rate)."""
        self.flush()
        rep = energy_from_stats(self.stats, n_masked=n_masked)
        total_pins = self.resident_hits + self.resident_misses
        return {
            "write_cycles": self.stats.n_write_cycles,
            "compare_cycles": self.stats.n_compare_cycles,
            "sets": int(self.stats.sets),
            "resets": int(self.stats.resets),
            "energy_write_j": rep.write_energy_j,
            "energy_compare_j": rep.compare_energy_j,
            "energy_total_j": rep.total_j,
            "makespan_cycles": self.makespan_cycles,
            "sequential_cycles": self.sequential_cycles,
            "makespan_ns": self.makespan_ns,
            "sequential_ns": self.sequential_ns,
            "n_graphs": self.n_graphs,
            "n_programs": self.n_programs,
            "pruned_write_cycles": self.pruned_write_cycles,
            "pruned_compare_cycles": self.pruned_compare_cycles,
            "emitted_passes": self.emitted_passes,
            "pruned_passes": self.pruned_passes,
            "resident_hits": self.resident_hits,
            "resident_misses": self.resident_misses,
            "resident_hit_rate": (self.resident_hits / total_pins
                                  if total_pins else 0.0),
            "weight_sparsity": (self.weight_zeros / self.weight_digits
                                if self.weight_digits else 0.0),
            # per-array power rollup; its energy_j is the SAME integer
            # counters priced through the SAME Table XI conversion as
            # energy_total_j, so the two agree bit-exactly
            "power": self.power.report(),
        }


class APServeContext:
    """Per-request AP serving state: runtime + aggregated stats/energy.

    ``x_levels`` is the activation quantization grid (|x_int| <= x_levels,
    e.g. 7 = a signed 4-level-per-sign 3-bit grid); the AP arithmetic on
    the quantized integers is exact, so output fidelity is set entirely by
    this knob.  ``reset()`` starts a fresh request; ``report()`` renders
    the aggregate as write/compare cycles, Table XI energy, and the
    occupancy model's makespan vs naive sequential drains.
    """

    def __init__(self, runtime: Runtime, *, radix: int = 3,
                 x_levels: int = 7, max_cols: int | None = None):
        self.runtime = runtime
        self.radix = radix
        self.x_levels = int(x_levels)
        self.max_cols = max_cols if max_cols is not None \
            else runtime.pool.cols
        # projection stacks (MoE experts, dense MLPs as stacks of one),
        # keyed by layer and projection: converted once (bounded by the
        # model: one per layer and projection)
        self._stacks: dict[str, APExpertStack] = {}
        # called with each MoE layer's dispatch record (see
        # ap_moe_dispatch), for checks that replay the layer
        self.on_moe: Callable[[dict], None] | None = None
        self._default_sink = APSink(radix=self.radix)

    def reset(self) -> None:
        self._default_sink.reset()

    def _sink(self) -> APSink:
        scope = _AP_SCOPE.get()
        return self._default_sink if scope is None else scope[0]

    # Aggregates read the *active* sink, so engine/report code written for
    # the sequential one-request-per-context contract keeps working both
    # standalone and inside an ap_request_scope.
    @property
    def stats(self) -> APStats:
        return self._sink().stats

    @property
    def makespan_cycles(self) -> int:
        return self._sink().makespan_cycles

    @property
    def sequential_cycles(self) -> int:
        return self._sink().sequential_cycles

    @property
    def makespan_ns(self) -> float:
        return self._sink().makespan_ns

    @property
    def sequential_ns(self) -> float:
        return self._sink().sequential_ns

    @property
    def n_graphs(self) -> int:
        return self._sink().n_graphs

    @property
    def n_programs(self) -> int:
        return self._sink().n_programs

    # -- projection stacks --------------------------------------------------

    def _resident_store(self) -> ResidentStore | None:
        return getattr(self.runtime.pool, "resident", None)

    def stack(self, key: str,
              make: Callable[..., APExpertStack] | None = None
              ) -> APExpertStack:
        """The projection stack of ``key`` (one layer's projection), made on
        first use by ``make(radix=, label=, store=)`` and pinned resident
        under the label ``key``; later calls return it whatever they are
        handed."""
        hit = self._stacks.get(key)
        if hit is None:
            if make is None:
                raise KeyError(f"no projection stack {key!r} converted yet")
            hit = make(radix=self.radix, label=key,
                       store=self._resident_store())
            self._stacks[key] = hit
        return hit

    def expert_stack(self, key: str, w_stack: jax.Array | None = None
                     ) -> APExpertStack:
        """The expert stack of ``key`` (one MoE layer's projection), made
        on first use from the float stack ``w_stack`` [E, K, N] (see
        :meth:`stack`)."""
        return self.stack(key, None if w_stack is None else
                          functools.partial(APExpertStack.from_dense,
                                            w_stack))

    # -- quantization -------------------------------------------------------

    def quantize(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """x float [T, K] -> (x_int int32 with |x| <= x_levels, scale)."""
        xf = jnp.asarray(x, jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(xf)) / self.x_levels, 1e-8)
        xi = jnp.clip(jnp.round(xf / s), -self.x_levels,
                      self.x_levels).astype(jnp.int32)
        return xi, s

    def quantize_rows(self, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """x float [T, K] -> (x_int int32 [T, K], scale [T]): each row on
        its own grid, |x_int| <= x_levels."""
        return quantize_rows_jnp(x, levels=self.x_levels)

    # -- execution + aggregation --------------------------------------------

    def run_graph(self, graph: ProgramGraph):
        scope = _AP_SCOPE.get()
        sink = self._default_sink if scope is None else scope[0]
        # builder-side meta (sparsity pruning, resident hits) folds here so
        # both the sequential and the wave-merged route account it
        sink.add_meta(graph.meta)
        if scope is not None and scope[1] is not None:
            # batched serving: hand the graph to the wave merger, which
            # coalesces it with the other in-flight requests' graphs and
            # settles this request's sink from its slice of the merged run
            return scope[1].run_graph(self, graph, scope[0])
        with trace.span("serve.graph", cat="serve", n_nodes=len(graph),
                        graph_index=sink.n_graphs):
            res = self.runtime.run_graph(graph, stats=sink.stats,
                                         collect_stats=True)
        sink.add_report(res.report)
        sink.defer_power(
            res.schedule, dict(res.traced),
            {i: n.label for i, n in enumerate(graph.nodes)},
            self.runtime.pool.n_arrays)
        return res

    def cache_stats(self) -> dict:
        """Occupancy of every compilation/serving cache this context rides:
        the process-wide bounded compile caches (:mod:`repro.apc.caches`),
        the pool's uploaded-schedule store, and the context's projection
        stacks — the numbers to watch in a long-running serve.Engine."""
        from .caches import cache_stats
        out = {
            "compile": cache_stats(),
            "pool_schedules": len(self.runtime.pool._schedules),
            "pool_schedules_max": self.runtime.pool._max_schedules,
            "stacks": len(self._stacks),
        }
        store = self._resident_store()
        if store is not None:
            out["resident"] = store.stats()
        return out

    def report(self, n_masked: int = N_MASKED_MAC) -> dict:
        """Aggregated per-request accounting: functional-simulator counters
        + Table XI energy + graph-scheduler occupancy (of the active
        sink — the default one outside :func:`ap_request_scope`)."""
        rep = self._sink().report(n_masked=n_masked)
        rep["n_arrays_total"] = getattr(self.runtime.pool, "total_arrays",
                                        self.runtime.pool.n_arrays)
        return rep


# ---------------------------------------------------------------------------
# MoE dispatch: every held (token, expert) pair's rows in one grouped MAC
# ---------------------------------------------------------------------------

def pair_slots(n: int, capacity: int, cap: int) -> int:
    """The (token, expert) pairs a grouped call launches for ``n`` held
    ones: the next power of two of ``max(n, capacity, 1)``, at most ``cap``
    (every routed pair).  The call pads to it with zero rows, so it has one
    of a few shapes whatever its routing, and launches at least the chip's
    expert ``capacity`` whether few pairs or none are held here."""
    return min(cap, 1 << (max(n, capacity, 1) - 1).bit_length())


@jax.jit
def _take_rows(x_int, scale, tok, n_real):
    """The rows (and row scales) of the tokens ``tok``; rows from
    ``n_real`` on are padding, zero (scale 1)."""
    real = jnp.arange(tok.shape[0]) < n_real
    return (jnp.where(real[:, None], x_int[tok], 0),
            jnp.where(real, scale[tok], 1.0))


@functools.partial(jax.jit, static_argnames=("act",))
def _glu(gate, up, *, act):
    return act(gate) * up


@functools.partial(jax.jit, static_argnames=("t",))
def _gated_sum(y, gates, pairs, tok, n_real, *, t: int) -> jax.Array:
    """Each held pair's row ``y`` [P, d] times its gate, summed per token
    into [t, d]; rows from ``n_real`` on are padding (gate 0)."""
    real = jnp.arange(pairs.shape[0]) < n_real
    g = jnp.where(real, gates[pairs].astype(jnp.float32), 0.0)
    return jnp.zeros((t, y.shape[1]), jnp.float32).at[tok].add(y * g[:, None])


def _expert_stack_of(w) -> APExpertStack:
    """A projection's experts as a stack (lists of per-expert
    :class:`APLinear` are stacked)."""
    return w if isinstance(w, APExpertStack) else \
        APExpertStack.from_linears(list(w))


def ap_moe_dispatch(ctx: APServeContext, x2d: jax.Array,
                    expert_ids: jax.Array, gates: jax.Array,
                    w1, w3, w2,
                    act: Callable[[jax.Array], jax.Array], *,
                    first: int = 0, capacity: int = 0,
                    layer: int | None = None) -> jax.Array:
    """SwiGLU MoE FFN over the experts held here, AP-served.

    ``x2d`` [T, d] float, ``expert_ids``/``gates`` [T, k] (the router's
    top-k over ALL experts, global ids, gates already normalised).
    ``w1``/``w3``/``w2`` are :class:`APExpertStack` (or equal-length lists
    of per-expert :class:`APLinear`) holding experts ``first ..
    first + E - 1``; pairs routed to other experts are left out — the
    result is ``sum_{e in top-k, held} g_e * FFN_e(x)``.

    The held pairs sort by expert on the host and pad, with zero rows that
    take no gate, to :func:`pair_slots` pairs: at least ``capacity`` (the
    chip's expert capacity), so the bank's work per call does not follow
    the routing below it, and a call has one of a few shapes.  Then TWO
    graphs run: the gate and up projections as one grouped MAC each over
    the rows of every pair (:meth:`APExpertStack.add_call`), then, after
    the float combine, the down projections as a third.  ``x`` quantises
    per token and the hidden ``h`` per (token, expert) row, ``|q| <=
    x_levels``.  Returns [T, d_out] float32.

    Counters: ``moe.pairs_routed`` and ``moe.pairs_held`` (pairs of the
    call), ``moe.layer_calls`` and ``moe.layers_empty`` (calls with a held
    pair and with none; both run on the bank).  With no token or no
    routing (T == 0 or k == 0) nothing runs and nothing counts.
    ``ctx.on_moe``, when set, gets the call's record: ``layer``,
    ``experts`` (the routed ids, [T, k]), ``x_levels`` [T, d], ``pairs``
    (token, global expert) [P, 2] of the held pairs, ``launched`` (the
    padded pair count) and ``h_levels`` [launched, d_ff], whose first P
    rows are the held pairs'.
    """
    if not isinstance(w1, APExpertStack) and not (
            len(w1) == len(w3) == len(w2)):
        raise ValueError(
            f"expert list lengths disagree: w1={len(w1)} "
            f"w3={len(w3)} w2={len(w2)}")
    if not isinstance(w2, APExpertStack) and not w2:
        raise ValueError("ap_moe_dispatch needs at least one expert")
    t, k = expert_ids.shape
    n_out = w2.n if isinstance(w2, APExpertStack) else w2[0].n
    if t == 0 or k == 0:                   # nothing routed: nothing runs
        return jnp.zeros((t, n_out), jnp.float32)
    w1, w3, w2 = (_expert_stack_of(w) for w in (w1, w3, w2))
    if not w1.e == w3.e == w2.e:
        raise ValueError(f"expert stacks disagree: {w1}, {w3}, {w2}")
    reg = get_registry()
    with trace.annotate("ap.moe.route"):
        local = np.asarray(expert_ids).reshape(-1) - first   # host sort
        held = np.nonzero((local >= 0) & (local < w1.e))[0]
        pairs = held[np.argsort(local[held], kind="stable")]
        tok, ex = pairs // k, local[pairs]
    reg.counter("moe.pairs_routed").inc(t * k)
    reg.counter("moe.pairs_held").inc(len(pairs))
    reg.counter("moe.layer_calls" if len(pairs) else
                "moe.layers_empty").inc()
    record = {"layer": layer, "experts": np.asarray(expert_ids),
              "pairs": np.stack([tok, ex + first], axis=1)}

    with trace.annotate("ap.moe.dispatch"):
        # padding rows: a zero input through the first held expert (or
        # expert 0), taking no gate
        n_pad = pair_slots(len(pairs), capacity, t * k)
        tok_p, ex_p, pairs_p = (np.concatenate(
            [a, np.full(n_pad - len(a), a[0] if len(a) else 0, a.dtype)])
            for a in (tok, ex, pairs))
        tok_j, pairs_j = jnp.asarray(tok_p, jnp.int32), \
            jnp.asarray(pairs_p, jnp.int32)
        n_real = jnp.int32(len(pairs))
        x_int, s_x = ctx.quantize_rows(x2d)
        xp, sp = _take_rows(x_int, s_x, tok_j, n_real)
        g1 = ProgramGraph()
        c1 = w1.add_call(g1, xp, ex_p, max_cols=ctx.max_cols,
                         max_q=ctx.x_levels)
        c3 = w3.add_call(g1, xp, ex_p, max_cols=ctx.max_cols,
                         max_q=ctx.x_levels)
    res1 = ctx.run_graph(g1)
    with trace.annotate("ap.moe.combine"):
        h = _glu(c1.decode(res1, sp), c3.decode(res1, sp), act=act)
    with trace.annotate("ap.moe.dispatch"):
        h_int, s_h = ctx.quantize_rows(h)
        g2 = ProgramGraph()
        c2 = w2.add_call(g2, h_int, ex_p, max_cols=ctx.max_cols,
                         max_q=ctx.x_levels)
    res2 = ctx.run_graph(g2)
    with trace.annotate("ap.moe.combine"):
        y2d = _gated_sum(c2.decode(res2, s_h), gates.reshape(-1), pairs_j,
                         tok_j, n_real, t=t)
    if ctx.on_moe is not None:
        ctx.on_moe(dict(record, x_levels=x_int, h_levels=h_int,
                        launched=n_pad))
    return y2d


# ---------------------------------------------------------------------------
# Serving hook: flip models' ternary projections onto the AP path
# ---------------------------------------------------------------------------

_AP_CTX: contextvars.ContextVar[APServeContext | None] = \
    contextvars.ContextVar("ap_serve_ctx", default=None)

# (sink, merger | None): set per request by the continuous-batching path so
# many requests can share one APServeContext without sharing accounting
_AP_SCOPE: contextvars.ContextVar[tuple | None] = \
    contextvars.ContextVar("ap_request_scope", default=None)


# the index of the model layer being served, set by the model's layer loop
# under an AP context (keys of per-layer state such as expert stacks)
_AP_LAYER: contextvars.ContextVar[int | None] = \
    contextvars.ContextVar("ap_layer", default=None)


@contextmanager
def ap_layer(index: int):
    """Mark the work inside as model layer ``index``'s."""
    token = _AP_LAYER.set(index)
    try:
        yield index
    finally:
        _AP_LAYER.reset(token)


def current_ap_layer() -> int | None:
    """The model layer being served, or None outside the layer loop."""
    return _AP_LAYER.get()


@contextmanager
def ap_request_scope(sink: APSink, merger=None):
    """Route this (thread's) AP work into ``sink`` instead of the context's
    default sink; with a ``merger`` (``serve.batcher.WaveMerger``), graph
    runs additionally rendezvous with the other in-flight requests into one
    row-concatenated merged graph per wave."""
    token = _AP_SCOPE.set((sink, merger))
    try:
        yield sink
    finally:
        _AP_SCOPE.reset(token)


def current_ap_context() -> APServeContext | None:
    """The active AP serving context, if any — None in ordinary float
    serving AND while jax is tracing (contextvars are visible during
    tracing, but the AP path is host-orchestrated and cannot live under
    jit, so a jitted step inside ``ap_serving`` falls back to the float
    path instead of exploding on a tracer host-sync)."""
    ctx = _AP_CTX.get()
    if ctx is None:
        return None
    clean = getattr(jax.core, "trace_state_clean", None)
    if clean is not None and not clean():
        return None
    return ctx


@contextmanager
def ap_serving(ctx: APServeContext):
    """While active, ``models.mlp.mlp`` (packed params) and
    ``models.moe.moe_ffn`` route their projections through ``ctx`` — the
    model code needs no plumbing, and the serve engine simply wraps its
    (unjitted) step."""
    token = _AP_CTX.set(ctx)
    try:
        yield ctx
    finally:
        _AP_CTX.reset(token)
