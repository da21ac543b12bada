"""AP program compiler: microcode IR + fused sharded executor.

The paper's methodology is a compiler in disguise — this package makes the
layers explicit and maps them back to the source sections:

==========================  =================================================
IR / compiler concept        Paper concept
==========================  =================================================
``ir.ApplyLUT``              One LUT-schedule application (§IV.A Table VII /
                             §V Table IX): the full compare/write pass list
                             for an in-place digit function at one digit
                             position.
``ir.ApplyLUT.extra_key``    Predicated execution: every compare key is
                             extended with exact matches (the shift-and-add
                             multiplier's "only rows with B_j == t" gate,
                             §IV methodology extended beyond the adder).
``ir.SetCol / ZeroCol``      The unconditional carry-clear write that opens
                             every multi-digit operation (§IV.C: C <- 0).
``ir.CompareWrite``          A single masked compare + write cycle (§III
                             Table III semantics) outside any LUT — used for
                             the multiply operand-repair sweeps that undo
                             the §IV.B cycle-breaking dummy write.
``ir.ForDigit``              Digit-serial ripple over the p positions of a
                             multi-digit word (§IV.C "the carry column
                             ripples across positions").
``lower.Step``               One compare-block + write cycle: the blocked
                             (§V, DFF latch) execution unit; non-blocked
                             passes are 1-key blocks.
``lower.CompiledProgram``    The whole program flattened to a static
                             schedule + packed to dense tensors — the
                             microcode store of the AP sequencer (Fouda et
                             al. tutorial's programmable-SIMD framing).
``exec.execute``             Row-parallel replay: all CAM rows take every
                             compare simultaneously (§II-III), fused so the
                             array stays resident across the entire program.
``stats.TracedStats``        The functional co-simulator counters (§VI:
                             Table V set/reset rules, mismatch histogram for
                             the matchline energy model) as in-graph
                             reductions.
``mac.mac_program``          The ternary dot-product as predicated add/sub
                             sweeps over weight digits — the AP-tutorial
                             vector-workload claim (Fouda et al. 2022)
                             compiled onto the serving path
                             (``ternary_matmul(..., impl="ap")``).
``mac.compile_mac_tiled``    The column budget made explicit: reductions
                             wider than one array split into per-tile
                             partial-sum programs (radix-complement mod
                             r^width) + a ripple-add reduction chain.
``pool.ArrayPool``           The AP *bank*: many bounded MvCAM arrays with
                             row-blocks double-buffered across them, one
                             shared schedule tensor, per-launch counters
                             concatenated into the global stats.
``graph.ProgramGraph``       A dependency DAG of compiled-program launches
                             (K-tile partial sums feeding their ripple-add
                             reduction; independent matmuls side by side) —
                             the multi-array scheduling problem the AP
                             tutorial (Fouda et al.) calls central at scale.
``runtime.DevicePool``       The bank spanned over a device mesh via
                             shard_map: n_arrays x n_devices physical
                             arrays, per-device schedule replay, APStats
                             psummed in-graph.
``runtime.Runtime``          Topological-wavefront executor + occupancy
                             model: independent programs pipeline into idle
                             arrays, ``graph_makespan`` extends
                             ``wall_cycles`` to whole graphs.
``layers.APLinear``          A model projection as a cached K-tiled MAC;
                             ``APServeContext`` aggregates per-request
                             APStats / Table XI energy across every AP-
                             served projection of a forward pass.
==========================  =================================================

Typical use::

    from repro import apc
    compiled = apc.compile_named("add", radix=3, width=20)
    out, traced = apc.execute(arr, compiled, collect_stats=True)
    stats = apc.to_ap_stats(traced, compiled, arr.shape[0], radix=3)

or via the drivers: ``repro.core.ap.ripple_add(..., engine="apc")``.
"""
from . import exec as exec  # noqa: PLC0414 — re-export the module
# (power.py joins graph_makespan schedules with TracedStats counters into
# per-array power/thermal timelines; see its module docstring)
from . import (caches as caches_mod, graph as graph_mod, ir,
               layers as layers_mod, lower, mac, metrics as metrics_mod,
               pool as pool_mod, power as power_mod,
               runtime as runtime_mod, stats, trace as trace_mod)
from .caches import (ResidentError, ResidentEvicted, ResidentHandle,
                     ResidentStale, ResidentStore, cache_stats,
                     clear_compile_caches)
from .exec import execute, execute_sharded, run
from .faults import (FaultConfig, FaultDetected, FaultModel,
                     fault_config_from_env, faults_enabled)
from .graph import (CARRIED, FoldStage, GraphNode, ProgramGraph,
                    fold_stage_input, graph_makespan, mac_fold_plan)
from .layers import (APExpertStack, APLinear, APServeContext, APSink,
                     ap_layer, ap_moe_dispatch, ap_request_scope, ap_serving,
                     current_ap_context, current_ap_layer, pair_slots)
from .runtime import DevicePool, GraphResult, Runtime
from .ir import (AffineCol, ApplyLUT, CompareWrite, ForDigit, Program,
                 RelCol, SetCol, ZeroCol, digit)
from .lower import (CompiledProgram, Step, compile_named, compile_program,
                    elementwise_program, lower as lower_program,
                    multiply_program, negate_program, ripple_add_program,
                    ripple_sub_program)
from .mac import (SUPPORT_DENSE, TiledMac, assemble_mac_rows_jnp,
                  compile_mac, compile_mac_reduce, compile_mac_tiled,
                  decode_mac_acc, decode_mac_acc_jnp,
                  decode_signed_digits_jnp, encode_mac_rows,
                  encode_mac_rows_jnp, encode_mac_x_rows_jnp,
                  encode_weight_digits_jnp, mac_acc_width, mac_layout,
                  mac_program, mac_reduce_program, mac_weight_support,
                  matmul_mac_rows, weight_digest)
from .metrics import MetricsRegistry, get_registry
from .pool import (ArrayPool, drain_fault_charges, resident_enabled,
                   run_mac_tiled, run_pooled)
from .power import (Counters, PowerAccum, PowerInterval, PowerTimeline,
                    emit_counter_tracks, graph_power, partition_blocks,
                    pool_power)
from .stats import TracedStats, accumulate, mac_sparsity, to_ap_stats
from .trace import (Tracer, current_tracer, global_tracer,
                    reset_global_tracer, tracing, validate_chrome_trace)

__all__ = [
    "caches_mod", "exec", "graph_mod", "ir", "layers_mod", "lower", "mac",
    "metrics_mod", "pool_mod", "runtime_mod", "stats", "trace_mod",
    "MetricsRegistry", "get_registry",
    "Tracer", "current_tracer", "global_tracer", "reset_global_tracer",
    "tracing", "validate_chrome_trace",
    "cache_stats", "clear_compile_caches",
    "ResidentError", "ResidentEvicted", "ResidentHandle", "ResidentStale",
    "ResidentStore",
    "execute", "execute_sharded", "run",
    "FaultConfig", "FaultDetected", "FaultModel", "fault_config_from_env",
    "faults_enabled", "drain_fault_charges",
    "CARRIED", "FoldStage", "GraphNode", "ProgramGraph", "fold_stage_input",
    "graph_makespan", "mac_fold_plan",
    "APExpertStack", "APLinear", "APServeContext", "APSink", "ap_layer",
    "ap_moe_dispatch", "ap_request_scope", "ap_serving",
    "current_ap_context", "current_ap_layer", "pair_slots",
    "DevicePool", "GraphResult", "Runtime",
    "AffineCol", "ApplyLUT", "CompareWrite", "ForDigit", "Program", "RelCol",
    "SetCol", "ZeroCol", "digit",
    "CompiledProgram", "Step", "compile_named", "compile_program",
    "elementwise_program", "lower_program", "multiply_program",
    "negate_program", "ripple_add_program", "ripple_sub_program",
    "SUPPORT_DENSE", "TiledMac", "assemble_mac_rows_jnp", "compile_mac",
    "compile_mac_reduce", "compile_mac_tiled",
    "decode_mac_acc", "decode_mac_acc_jnp", "decode_signed_digits_jnp",
    "encode_mac_rows", "encode_mac_rows_jnp", "encode_mac_x_rows_jnp",
    "encode_weight_digits_jnp", "mac_acc_width", "mac_layout",
    "mac_program", "mac_reduce_program", "mac_weight_support",
    "matmul_mac_rows", "weight_digest",
    "ArrayPool", "resident_enabled", "run_mac_tiled", "run_pooled",
    "power_mod", "Counters", "PowerAccum", "PowerInterval", "PowerTimeline",
    "emit_counter_tracks", "graph_power", "partition_blocks", "pool_power",
    "TracedStats", "accumulate", "mac_sparsity", "to_ap_stats",
]
