"""A run at a small size on the CPU: correct as it is, and not correct with
the timed path broken underneath or with the configuration's control."""
import pytest

import run

VEC = {"config_override": {"assumed": {"bank": {"n_arrays": 2, "rows": 64,
                                                 "cols": 64}}},
       "traffic_override": {"job": {"rows": {"values": [200, 64, 10]},
                                    "operand_set": {"range": 2}},
                            "check_sample": 1000}}
CHAT = "qwen3-0.6b-l1.chat-c2"
SERVE = {"config_override": {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 32768,
    "assumed": {"bank": {"n_arrays": 2, "rows": 64, "cols": 128},
                "x_levels": 7}},
    # short prompts, so that served tokens come within a few waves
    "traffic_override": {"job": {"prompt_tokens": {"values": [1, 2]},
                                 "new_tokens": {"values": [2, 3]}}}}
SERVE_SECONDS = 10.0


def vec_run(seed=3, **kw):
    args = {**VEC, **kw}
    return run.run_cell("tap-add-r3w20.short", seed, 1.0, False,
                        require_chip=False, **args)[0]


def serve_run(seed=5, **kw):
    return run.run_cell(CHAT, seed, SERVE_SECONDS, False, require_chip=False,
                        **SERVE, **kw)[0]


def test_vec_run_is_correct():
    line = vec_run()
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "vec_rows_per_s",
                                    "vec_job_p95_ms"}
    assert list(line)[-1] == "checks"


def _pooled(fault):
    from repro.apc import pool as pool_mod
    real = pool_mod.run_pooled

    def broken(arr, compiled, pool, *, stats=None, **kw):
        import jax.numpy as jnp
        if fault == "altered":
            out = real(arr, compiled, pool, stats=stats, **kw)
            return out.at[0, 21].set((out[0, 21] + 1) % 3)
        if fault == "unchanged":
            return jnp.asarray(arr)
        half = arr.shape[0] // 2                  # half of the rows left out
        out = real(arr[:half], compiled, pool, stats=stats, **kw)
        return jnp.concatenate([out, jnp.asarray(arr[half:])])
    return broken


@pytest.mark.parametrize("fault", ["altered", "unchanged", "half"])
def test_vec_run_with_a_fault_is_not_correct(monkeypatch, fault):
    from repro.apc import pool as pool_mod
    monkeypatch.setattr(pool_mod, "run_pooled", _pooled(fault))
    assert not vec_run()["correct"]


def test_vec_control_is_not_correct():
    line = vec_run(config_override={**VEC["config_override"],
                                    "lut": "blocked"})
    assert not line["correct"]
    assert line["checks"]["jobs_with_wrong_apstats"]["value"] > 0
    assert line["checks"]["sampled_rows_with_wrong_digits"]["value"] == 0


def test_serve_run_is_correct():
    line = serve_run()
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "serve_tok_per_s"}
    assert line["checks"]["served_not_greedy"]["value"] == 0


def test_serve_token_altered_is_not_correct(monkeypatch):
    from repro.serve.engine import Engine
    real = Engine._sample

    def altered(self, logits, key):
        return (real(self, logits, key) + 1) % logits.shape[-1]
    monkeypatch.setattr(Engine, "_sample", altered)
    line = serve_run()
    assert not line["correct"]
    assert line["checks"]["served_not_greedy"]["value"] > 0


def test_serve_state_unchanged_is_not_correct(monkeypatch):
    from repro.models import attention
    monkeypatch.setattr(attention, "decode_update_cache",
                        lambda cache, k, v, pos, ring: cache)
    line = serve_run(seed=7)
    assert not line["correct"]
    assert line["checks"]["logit_rel_gap"]["value"] > \
        line["checks"]["logit_rel_gap"]["limit"]


def test_serve_half_the_rows_left_out_is_not_correct(monkeypatch):
    from repro.apc.pool import ArrayPool
    real = ArrayPool.run

    def half(self, arr, compiled, **kw):
        out, traced = real(self, arr, compiled, **kw)
        return out.at[out.shape[0] // 2:].set(0), traced
    monkeypatch.setattr(ArrayPool, "run", half)
    line = serve_run()
    assert not line["correct"]
    assert line["checks"]["logit_rel_gap"]["value"] > \
        line["checks"]["logit_rel_gap"]["limit"]


def test_serve_control_is_not_correct():
    line, checks = run.run_cell(
        CHAT, 9, SERVE_SECONDS, False, require_chip=False, **SERVE,
        check_kwargs={"precision": "fp8"})
    assert not line["correct"]
    gap = checks[0]
    assert gap["value"] > gap["limit"] > gap["program"]
