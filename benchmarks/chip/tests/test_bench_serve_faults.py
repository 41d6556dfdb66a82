"""A whole run of the serving cell at a tiny size on the CPU, past the
harness's look for a chip: sound, `correct` comes out true and nothing
compiles in the window; with each served token altered where the
engine's step produces it, `correct` comes out false."""
import jax.numpy as jnp

import bench_tiny

CELL = bench_tiny.SERVE


def test_sound_run_is_correct_and_compiles_nothing_in_the_window(
        tmp_path, capsys):
    logs = []
    import json

    import jax
    import run as harness

    root, data = bench_tiny.tiny_root(tmp_path)
    res = harness.run_cell(root, CELL, 11, 1.0, 0, jax.devices(),
                           bench=json.loads((root / "BENCHMARK.json")
                                            .read_text()),
                           data=data, log=logs.append)
    assert res["correct"], res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"serve_tokens_per_s", "setup_s",
                                   "request_latency_p95_ms", "itl_p95_ms"}
    assert [m for m in logs if m.startswith("compiles_in_window=0 ")], logs


def test_altered_tokens_are_not_correct(tmp_path, monkeypatch):
    import repro.models.transformer as tf

    orig = tf.logits_fn
    monkeypatch.setattr(tf, "logits_fn", lambda cfg, params, x: jnp.roll(
        orig(cfg, params, x), 1, axis=-1))
    res = bench_tiny.run_tiny(tmp_path, CELL, seed=12)
    assert not res["correct"], res["compared"]
