# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device; only launch/dryrun.py requests 512 placeholders.
import numpy as np
import pytest


@pytest.fixture(scope="session")
def smoke_mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("pod", "data", "model"))


@pytest.fixture
def profiled(tmp_path):
    """``profiled(fn)`` runs ``fn`` under the JAX profiler and returns
    ``(fn's result, spans)``: the program's ``ap.*`` spans of the trace, as
    ``{host thread: [(name, start_ns, end_ns), ...]}`` in start order."""
    import glob
    import tempfile

    import jax

    def run(fn):
        log_dir = tempfile.mkdtemp(dir=tmp_path)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        spans = {}
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                evs = [(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events if e.name.startswith("ap.")]
                if evs:
                    spans[(plane.name, i)] = sorted(evs, key=lambda e: e[1])
        return out, spans

    return run
