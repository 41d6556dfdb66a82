"""A whole run of each training cell at a tiny size on the CPU, past the
harness's look for a chip: sound, `correct` comes out true, and with
the timed path broken underneath in each way the cell can be broken,
it comes out false."""
import jax
import pytest

import bench_tiny


def _wrap_step(monkeypatch, wrap):
    import repro.launch.train as train_mod

    orig = train_mod.make_train_step

    def broken(model, tcfg):
        return wrap(orig(model, tcfg))

    monkeypatch.setattr(train_mod, "make_train_step", broken)


def _state_unchanged(step_fn):
    def step(state, batch, i):
        _, metrics = step_fn(state, batch, i)
        return state, metrics
    return step


def _half_batch(step_fn):
    def step(state, batch, i):
        half = {k: v[:, : v.shape[1] // 2] for k, v in batch.items()}
        return step_fn(state, half, i)
    return step


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch}
CELLS = ["qwen2-0.5b.apibcd-a1", bench_tiny.RING]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, cell):
    res = bench_tiny.run_tiny(tmp_path, cell)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_step_is_not_correct(tmp_path, monkeypatch, cell, fault):
    _wrap_step(monkeypatch, FAULTS[fault])
    res = bench_tiny.run_tiny(tmp_path, cell)
    assert not res["correct"], res["compared"]


def test_ring_without_exchange_is_not_correct(tmp_path, monkeypatch):
    monkeypatch.setattr(jax.lax, "ppermute",
                        lambda x, axis_name, perm: x)
    res = bench_tiny.run_tiny(tmp_path, bench_tiny.RING)
    assert not res["correct"], res["compared"]
