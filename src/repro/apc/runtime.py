"""AP runtime: program-graph scheduler over a device-sharded array pool.

Two layers on top of the PR-3 :class:`~repro.apc.pool.ArrayPool`:

- :class:`DevicePool` — the pool's array bank generalized to span a device
  mesh via ``shard_map``: ONE pool of ``n_arrays * n_devices`` physical
  MvCAM arrays.  Rows shard over the mesh's batch axes, every device
  replays the same uploaded schedule tensors against its local bank
  (blocks of ``rows`` rows, the kernel grid), and the traced APStats
  counters are ``psum``-ed in-graph so every shard returns the global
  counts — output digits and accumulated APStats stay bit-identical to a
  single-array :func:`~repro.apc.exec.execute`.

- :class:`Runtime` — executes a :class:`~repro.apc.graph.ProgramGraph`:
  nodes run in topological wavefronts, every ready node's launch is issued
  before any launch of the wave is drained (jax dispatch is asynchronous,
  so independent programs pipeline into idle arrays instead of draining
  each launch), dependency results flow node-to-node on device, and each
  node's schedule-static cycles + traced counters fold into one APStats.
  :meth:`Runtime.makespan` prices the same graph with the per-array
  occupancy model (:func:`~repro.apc.graph.graph_makespan`) — the graph
  generalization of ``ArrayPool.wall_cycles``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.ap import APStats
from ..kernels.tap_pass.ops import _pad_rows
from ..launch.mesh import data_axes
from . import trace
from .exec import sharded_program_run
from .faults import FaultDetected
from .graph import ProgramGraph, graph_makespan
from .lower import CompiledProgram
from .metrics import get_registry
from .pool import ArrayPool, drain_fault_charges
from .stats import HIST_BINS, TracedStats, accumulate

__all__ = ["DevicePool", "Runtime", "GraphResult"]


class DevicePool(ArrayPool):
    """An :class:`ArrayPool` whose bank spans the devices of a mesh.

    ``mesh=None`` degrades to the single-device ArrayPool (same dispatch
    loop); with a mesh, ``run`` shard_maps row-shards over the mesh's
    batch axes (``pod``/``data``, falling back to the first axis), each
    device streaming its shard through ``n_arrays`` local arrays.
    """

    def __init__(self, mesh=None, *, n_arrays: int = 4, rows: int = 4096,
                 cols: int = 256, interpret: bool | None = None,
                 resident_slots: int = 256, faults=None):
        super().__init__(n_arrays=n_arrays, rows=rows, cols=cols,
                         interpret=interpret, resident_slots=resident_slots,
                         faults=faults)
        if mesh is not None and self.fault_model is not None:
            raise NotImplementedError(
                "fault injection runs on the host pool path; the shard_map "
                "route has no per-block recovery hook yet")
        self.mesh = mesh
        if mesh is None:
            self.axes: tuple[str, ...] = ()
            self.n_devices = 1
        else:
            self.axes = data_axes(mesh) or tuple(mesh.axis_names[:1])
            self.n_devices = math.prod(mesh.shape[a] for a in self.axes)

    def __repr__(self) -> str:
        return (f"DevicePool(n_devices={self.n_devices}, "
                f"n_arrays={self.n_arrays}, rows={self.rows}, "
                f"cols={self.cols})")

    @property
    def total_arrays(self) -> int:
        return self.n_arrays * self.n_devices

    def n_blocks_per_device(self, n_rows: int) -> int:
        return -(-self.n_blocks(n_rows) // self.n_devices)

    def wall_cycles(self, n_rows: int, n_compare_cycles: int,
                    n_write_cycles: int) -> dict[str, int]:
        """Pipelined wall clock: blocks split over devices first, then each
        device's share streams over its local arrays —
        ``ceil(ceil(blocks / devices) / arrays)`` replay waves."""
        waves = max(1, -(-self.n_blocks_per_device(max(1, n_rows))
                         // self.n_arrays))
        return {"waves": waves,
                "compare_cycles": waves * n_compare_cycles,
                "write_cycles": waves * n_write_cycles}

    def run(self, arr: jax.Array, compiled: CompiledProgram, *,
            collect_stats: bool = False, interpret: bool | None = None,
            block_valid: tuple[int, ...] | None = None,
            radix: int | None = None
            ) -> tuple[jax.Array, TracedStats | None]:
        """Stream [rows, cols] digit rows through the device-spanning bank.

        Bit-identical output and (when ``collect_stats``) APStats to the
        single-array :func:`~repro.apc.exec.execute` — padding rows are
        masked per shard and the per-block counters psum across devices.
        """
        if self.mesh is None:
            return super().run(arr, compiled, collect_stats=collect_stats,
                               interpret=interpret, block_valid=block_valid,
                               radix=radix)
        if block_valid is not None:
            raise NotImplementedError(
                "row-concatenated (block_valid) launches run on the host "
                "pool path; the shard_map route masks per-shard rows only")
        n_rows, n_cols = arr.shape
        self.validate(compiled, n_cols=n_cols)
        interpret = self.interpret if interpret is None else interpret
        if n_rows == 0:
            empty = jnp.zeros((1, 2 + HIST_BINS), jnp.int32)
            return (jnp.asarray(arr, jnp.int8),
                    TracedStats(empty) if collect_stats else None)
        sched = self._device_schedule(compiled)
        d = self.n_devices
        # per-device shard: whole blocks of self.rows (kernel grid splits
        # the shard back into per-array blocks); padding rows are masked
        # per shard and the counters psummed by the shared scaffolding
        rows_per_dev = -(-n_rows // d)
        shard_rows = self.rows * max(1, -(-rows_per_dev // self.rows))
        with trace.annotate("ap.pool.run"):
            with trace.span("devicepool.run", cat="pool",
                            prof="ap.pool.launch", rows=n_rows, n_devices=d,
                            n_arrays=self.n_arrays,
                            steps=compiled.n_steps):
                padded, _ = _pad_rows(jnp.asarray(arr, jnp.int8),
                                      d * shard_rows)
                out, raw = sharded_program_run(
                    padded, sched, self.mesh, self.axes, n_rows, self.rows,
                    collect_stats=collect_stats, interpret=interpret)
            out = out[:n_rows]
        if collect_stats:
            return out, TracedStats(raw)
        return out, None


class GraphResult(dict):
    """``{node_id: result array}`` plus the run's occupancy report.

    ``traced`` carries each node's per-block
    :class:`~repro.apc.stats.TracedStats` when the run collected counters
    (``stats`` given or ``collect_stats=True``) — the batching layer
    splits these per request slice (:class:`~repro.apc.graph.MergedSlice`)
    to attribute a shared wave's counters exactly.

    ``schedule`` is the occupancy model's per-(node, array) interval
    record (see :func:`~repro.apc.graph.graph_makespan`) — together with
    ``traced`` it is everything :func:`repro.apc.power.graph_power` needs
    to build the per-array power timeline.
    """

    def __init__(self, results: dict[int, jax.Array],
                 report: dict[str, float],
                 traced: dict[int, "TracedStats | None"] | None = None,
                 schedule: list[dict] | None = None):
        super().__init__(results)
        self.report = report
        self.traced = traced or {}
        self.schedule = schedule or []


class Runtime:
    """Schedules :class:`ProgramGraph` nodes over an array pool.

    One runtime per pool; graphs are transient.  ``stats`` accumulation is
    per node (schedule-static cycles + traced counters), so running a
    graph charges exactly what running each program alone would.
    """

    def __init__(self, pool: ArrayPool, *, interpret: bool | None = None):
        self.pool = pool
        self.interpret = interpret
        self.last_report: dict[str, float] | None = None

    def __repr__(self) -> str:
        return f"Runtime(pool={self.pool!r})"

    @property
    def n_devices(self) -> int:
        return getattr(self.pool, "n_devices", 1)

    def check_knobs(self, *, interpret: bool | None = None) -> None:
        """Reject a per-call ``interpret`` the runtime route cannot honor.

        Graph execution always runs with the Runtime's own ``interpret``; a
        caller passing a different explicit value would otherwise be
        silently ignored — raise instead and point at the constructor.  An
        explicit value that merely restates what an unconfigured (None)
        Runtime resolves to anyway is compatible — e.g. ``interpret=True``
        against a default Runtime on a CPU host.
        """
        from ..kernels.tap_pass.kernel import resolve_interpret
        if interpret is None or interpret == self.interpret:
            return
        if self.interpret is None and interpret == resolve_interpret(None):
            return
        raise ValueError(
            f"interpret={interpret!r} conflicts with "
            f"Runtime(interpret={self.interpret!r}) — the graph route runs "
            f"with the Runtime's own; set it on the Runtime constructor")

    def makespan(self, graph: ProgramGraph,
                 record: list | None = None) -> dict[str, float]:
        """Occupancy-model makespan of ``graph`` on this runtime's bank
        (``record`` captures the per-array schedule; see
        :func:`~repro.apc.graph.graph_makespan`)."""
        return graph_makespan(graph, n_arrays=self.pool.n_arrays,
                              rows_per_array=self.pool.rows,
                              n_devices=self.n_devices, record=record,
                              dead_arrays=getattr(self.pool, "dead_arrays",
                                                  ()))

    def run_graph(self, graph: ProgramGraph, *,
                  stats: APStats | None = None,
                  order: list[int] | None = None,
                  collect_stats: bool = False) -> GraphResult:
        """Execute the graph; returns every node's result keyed by node id.

        ``order`` overrides the default wavefront order with any valid
        topological linearization — results are bit-identical regardless
        (node builds are pure functions of dependency results), which the
        scheduler property tests pin down.

        ``collect_stats=True`` collects per-node traced counters into
        ``GraphResult.traced`` without aggregating them anywhere — the
        serving batcher's route, which attributes each merged node's
        counters to its per-request slices itself.
        """
        nodes = graph.nodes
        waves = graph.wavefronts()
        if order is None:
            order = [nid for wave in waves for nid in wave]
        if sorted(order) != list(range(len(nodes))):
            raise ValueError("order must be a permutation of all node ids")
        done: set[int] = set()
        results: dict[int, jax.Array] = {}
        traced: list[tuple[int, TracedStats | None]] = []
        collect = stats is not None or collect_stats
        tracer = trace.current_tracer()
        wave_of = {nid: w for w, ws in enumerate(waves) for nid in ws}
        with trace.span("run_graph", cat="runtime",
                        prof="ap.runtime.run_graph", n_nodes=len(nodes),
                        n_waves=len(waves)) as gspan:
            # per-wavefront spans: a new one opens whenever the dispatch
            # order crosses a wavefront boundary, so a custom (non-wave-
            # major) order shows up as the same wavefront re-opening —
            # predicted occupancy vs actual dispatch order, on one track
            wave_span = None
            cur_wave = None
            try:
                for pos, nid in enumerate(order):
                    node = nodes[nid]
                    if any(d not in done for d in node.deps):
                        raise ValueError(
                            f"order runs node {nid} before its dependencies "
                            f"{tuple(d for d in node.deps if d not in done)}")
                    if tracer is not None and wave_of[nid] != cur_wave:
                        if wave_span is not None:
                            wave_span.__exit__(None, None, None)
                        cur_wave = wave_of[nid]
                        wave_span = tracer.span(
                            f"wavefront{cur_wave}", cat="runtime",
                            wave=cur_wave,
                            width=len(waves[cur_wave])).__enter__()
                    with trace.span(node.label or f"node{nid}", cat="node",
                                    node=nid, rows=node.rows,
                                    dispatch_order=pos, wave=wave_of[nid],
                                    compare_cycles=(
                                        node.compiled.n_compare_cycles),
                                    write_cycles=node.compiled.n_write_cycles,
                                    deps=list(node.deps)):
                        with trace.annotate("ap.model.graph_build"):
                            arr = node.build(*(results[d]
                                               for d in node.deps))
                        if arr.ndim != 2 or arr.shape[0] != node.rows:
                            raise ValueError(
                                f"node {nid} ({node.label or 'unlabeled'}) "
                                f"built a {arr.shape} array, declared "
                                f"rows={node.rows}")
                        # issue the launch; jax dispatch is async, so
                        # launches of independent nodes in the same
                        # wavefront overlap in flight — the pool's own
                        # double buffering spreads blocks over arrays
                        fm = getattr(self.pool, "fault_model", None)
                        attempts = 1 + (fm.cfg.node_retries
                                        if fm is not None else 0)
                        for t in range(attempts):
                            try:
                                out, tr = self.pool.run(
                                    arr, node.compiled,
                                    collect_stats=collect,
                                    interpret=self.interpret,
                                    block_valid=node.block_valid,
                                    radix=graph.radix)
                                break
                            except FaultDetected as e:
                                # re-execute ONLY this node: deps are done
                                # and their results live; the whole-node
                                # replay redraws transient faults on a
                                # (possibly just-degraded) bank
                                e.node = nid
                                if t + 1 >= attempts:
                                    raise
                                get_registry().counter(
                                    "faults.node_retries").inc()
                                trace.fault("node_retry", node=nid,
                                            attempt=t + 1)
                    results[nid] = node.result(out)
                    traced.append((nid, tr))
                    done.add(nid)
            finally:
                if wave_span is not None:
                    wave_span.__exit__(None, None, None)
            if stats is not None:
                for nid, tr in traced:
                    accumulate(stats, tr, nodes[nid].compiled,
                               n_rows=nodes[nid].rows,
                               label=nodes[nid].label or f"node{nid}")
            drain_fault_charges(self.pool, stats)
            rec: list = []
            res = GraphResult(results, self.makespan(graph, record=rec),
                              traced=dict(traced) if collect else None,
                              schedule=rec)
            if tracer is not None:
                gspan.set(makespan_cycles=res.report["makespan_cycles"],
                          sequential_cycles=res.report["sequential_cycles"],
                          makespan_ns=res.report["makespan_ns"],
                          sequential_ns=res.report["sequential_ns"])
                # render the occupancy model's per-array schedule as the
                # model-time timeline, anchored under this graph's host span
                base = gspan.ts_ns
                for iv in rec:
                    dev, a = divmod(iv["array"], self.pool.n_arrays)
                    tracer.model_span(
                        nodes[iv["node"]].label or f"node{iv['node']}",
                        track=f"dev{dev}/arr{a}",
                        start_ns=base + iv["start_ns"],
                        dur_ns=iv["end_ns"] - iv["start_ns"],
                        node=iv["node"], blocks=iv["blocks"],
                        cycles=iv["end_cycles"] - iv["start_cycles"])
                if collect:
                    # power counter tracks: the same schedule joined with
                    # the per-node traced counters (exact partition)
                    from .power import graph_power, emit_counter_tracks
                    from .layers import N_MASKED_MAC
                    tl = graph_power(
                        rec, res.traced, radix=graph.radix or 3,
                        n_masked=N_MASKED_MAC,
                        n_arrays_local=self.pool.n_arrays,
                        labels={i: n.label for i, n in enumerate(nodes)})
                    emit_counter_tracks(tracer, tl, base_ns=base)
        self.last_report = res.report
        return res

    def run_mac_graph(self, macs, *, stats: APStats | None = None
                      ) -> list[jax.Array]:
        """Convenience: run many independent K-tiled MACs as ONE graph.

        ``macs`` is a sequence of ``(x, w_ter, tiled)`` triples (see
        :meth:`ProgramGraph.add_mac_tiled`); returns the [R, width]
        accumulator digit block of each MAC, scheduled with all tile
        programs interleaved across the bank.
        """
        graph = ProgramGraph()
        finals = [graph.add_mac_tiled(x, w, tiled, label=f"mac{i}:")
                  for i, (x, w, tiled) in enumerate(macs)]
        res = self.run_graph(graph, stats=stats)
        return [res[f] for f in finals]
