"""Tests for `repro.analysis` — the repo's static-analysis pass.

Three layers:

  * per-rule positive/negative fixtures (string snippets through
    `run_source`; a fixture string never trips the linter when this
    file itself is linted, because string contents aren't AST),
  * regression-injection tests: re-introducing the historical bug into
    the REAL source of `benchmarks/bench_serving.py` /
    `dist/async_schedule.py` / the kernels must produce a finding
    (ISSUE 7 acceptance criteria),
  * the tier-1 gate: the repo itself is lint-clean modulo the committed
    baseline, plus pragma/baseline round-trips and CLI exit codes.
"""
import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

from repro.analysis import RULES, run_paths, run_source
from repro.analysis import baseline as baseline_mod
from repro.analysis.pragmas import parse_pragmas

ROOT = pathlib.Path(__file__).resolve().parents[1]
LINT_TREES = ["src", "tests", "benchmarks", "examples", "chip_smoke.py"]


def lint(src, path="fixture.py"):
    report = run_source(textwrap.dedent(src), path)
    assert not report.errors, report.errors
    return report


def rules_hit(src, path="fixture.py"):
    return {f.rule for f in lint(src, path).active}


# ---------------------------------------------------------------------------
# rule registry / plumbing
# ---------------------------------------------------------------------------

EXPECTED_RULES = {
    "wall-clock-duration", "quadratic-queue", "host-sync-in-hot-loop",
    "recompile-hazard", "nondeterminism-in-dist", "pallas-kernel-contract",
    "pallas-blockspec-shape",
}


def test_all_rules_registered():
    assert EXPECTED_RULES <= set(RULES), sorted(RULES)


def test_syntax_error_reported_not_raised():
    report = run_source("def broken(:\n", "bad.py")
    assert report.errors and "parse error" in report.errors[0]


# ---------------------------------------------------------------------------
# wall-clock-duration
# ---------------------------------------------------------------------------

def test_wall_clock_subtraction_flagged():
    assert "wall-clock-duration" in rules_hit("""
        import time
        t0 = time.time()
        wall = time.time() - t0
    """)


def test_wall_clock_deadline_compare_flagged():
    assert "wall-clock-duration" in rules_hit("""
        import time
        deadline = time.time() + 30
        while time.time() < deadline:
            pass
    """)


def test_wall_clock_indirect_name_subtraction_flagged():
    # both operands are names; the calls themselves are bare
    assert "wall-clock-duration" in rules_hit("""
        import time
        t0 = time.time()
        t1 = time.time()
        wall = t1 - t0
    """)


def test_wall_clock_from_import_alias_flagged():
    assert "wall-clock-duration" in rules_hit("""
        from time import time
        t0 = time()
        wall = time() - t0
    """)


def test_bare_timestamp_not_flagged():
    assert "wall-clock-duration" not in rules_hit("""
        import time
        record = {"timestamp": time.time()}
    """)


def test_monotonic_duration_not_flagged():
    assert not rules_hit("""
        import time
        t0 = time.monotonic()
        wall = time.monotonic() - t0
        t1 = time.perf_counter()
        fine = time.perf_counter() - t1
    """)


# ---------------------------------------------------------------------------
# quadratic-queue
# ---------------------------------------------------------------------------

def test_list_pop0_flagged():
    assert "quadratic-queue" in rules_hit("""
        class S:
            def drain(self):
                while self.queue:
                    item = self.queue.pop(0)
    """)


def test_list_insert0_flagged():
    assert "quadratic-queue" in rules_hit("""
        def requeue(q, item):
            q.insert(0, item)
    """)


def test_sys_path_insert_not_flagged():
    assert "quadratic-queue" not in rules_hit("""
        import sys
        sys.path.insert(0, "src")
    """)


def test_deque_popleft_and_tail_ops_not_flagged():
    assert "quadratic-queue" not in rules_hit("""
        from collections import deque
        q = deque()
        q.append(1)
        q.popleft()
        q.pop()
        lst = [3, 1]
        lst.insert(2, 9)
        lst.pop()
    """)


# ---------------------------------------------------------------------------
# host-sync-in-hot-loop
# ---------------------------------------------------------------------------

def test_asarray_in_hot_loop_flagged():
    assert "host-sync-in-hot-loop" in rules_hit("""
        import numpy as np
        from repro.utils.hotpath import hot_loop

        @hot_loop
        def step(toks_dev):
            return np.asarray(toks_dev)
    """)


def test_item_float_device_get_in_hot_loop_flagged():
    report = lint("""
        import jax
        from repro.utils import hot_loop

        @hot_loop
        def step(x):
            a = x.item()
            b = float(x)
            c = jax.device_get(x)
            return a, b, c
    """)
    assert sum(f.rule == "host-sync-in-hot-loop" for f in report.active) == 3


def test_sync_outside_hot_loop_not_flagged():
    assert "host-sync-in-hot-loop" not in rules_hit("""
        import numpy as np

        def cold_path(x):
            return float(np.asarray(x))
    """)


def test_runtime_hot_loop_marker_is_identity():
    from repro.utils import hot_loop

    def f(x):
        return x + 1

    g = hot_loop(f)
    assert g is f and g.__hot_loop__ and g(1) == 2


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------

def test_dict_of_jitted_fns_flagged():
    assert "recompile-hazard" in rules_hit("""
        import jax

        class Server:
            def prefill_fn(self, length, fn):
                self._prefill_fns[length] = jax.jit(fn)
    """)


def test_jit_in_loop_flagged():
    assert "recompile-hazard" in rules_hit("""
        import jax

        def run(fns, x):
            for fn in fns:
                x = jax.jit(fn)(x)
            return x
    """)


def test_unhashable_static_arg_flagged():
    assert "recompile-hazard" in rules_hit("""
        import jax

        step = jax.jit(kernel, static_argnums=(1,))
        out = step(x, [128, 256])
    """)


def test_bounded_jit_and_hashable_static_not_flagged():
    assert "recompile-hazard" not in rules_hit("""
        import jax

        step = jax.jit(kernel, static_argnums=(1,))
        out = step(x, (128, 256))
        decode = jax.jit(decode_fn, donate_argnums=(2,))
    """)


# ---------------------------------------------------------------------------
# nondeterminism-in-dist
# ---------------------------------------------------------------------------

DIST_PATH = "src/repro/dist/async_schedule.py"


def test_set_iteration_in_dist_flagged():
    assert "nondeterminism-in-dist" in rules_hit("""
        def apply_all(deltas):
            for d in set(deltas):
                apply(d)
    """, DIST_PATH)


def test_dict_values_iteration_in_dist_flagged():
    assert "nondeterminism-in-dist" in rules_hit("""
        def apply_all(pending):
            total = [v for v in pending.values()]
            return total
    """, DIST_PATH)


def test_unseeded_rng_and_wall_clock_in_dist_flagged():
    report = lint("""
        import random
        import numpy as np
        import time

        def jitter():
            a = random.random()
            b = np.random.default_rng()
            now = time.time()
            return a, b, now
    """, DIST_PATH)
    assert sum(f.rule == "nondeterminism-in-dist"
               for f in report.active) == 3


def test_blessed_forms_in_dist_not_flagged():
    assert "nondeterminism-in-dist" not in rules_hit("""
        import time
        import numpy as np

        def walk(seed, proc, pending):
            rng = np.random.default_rng((seed, proc))
            for k in sorted(pending.values()):
                pass
            t0 = time.monotonic()
            return time.monotonic() - t0
    """, DIST_PATH)


def test_same_code_outside_dist_modules_not_flagged():
    assert "nondeterminism-in-dist" not in rules_hit("""
        def apply_all(deltas):
            for d in set(deltas):
                apply(d)
    """, "src/repro/serve/engine.py")


# ---------------------------------------------------------------------------
# pallas-kernel-contract
# ---------------------------------------------------------------------------

PALLAS_OK = """
    import jax
    from jax.experimental import pallas as pl

    def call(kern, x, bq, hd, s):
        grid = (4, pl.cdiv(s, bq))
        return pl.pallas_call(
            kern,
            grid=grid,
            in_specs=[pl.BlockSpec((1, bq, hd), lambda h, qi: (h, qi, 0))],
            out_specs=pl.BlockSpec((1, bq, hd), lambda h, qi: (h, qi, 0)),
            out_shape=jax.ShapeDtypeStruct((4, s, hd), x.dtype),
        )(x)
"""


def test_pallas_consistent_call_not_flagged():
    assert "pallas-kernel-contract" not in rules_hit(PALLAS_OK)


def test_pallas_index_map_arity_mismatch_flagged():
    bad = PALLAS_OK.replace("lambda h, qi: (h, qi, 0))],",
                            "lambda h: (h, 0, 0))],")
    assert "pallas-kernel-contract" in rules_hit(bad)


def test_pallas_default_args_dont_count_toward_arity():
    ok = PALLAS_OK.replace("lambda h, qi: (h, qi, 0))],",
                           "lambda h, qi, g=2: (h // g, qi, 0))],")
    assert "pallas-kernel-contract" not in rules_hit(ok)


def test_pallas_shape_vs_return_len_flagged():
    bad = PALLAS_OK.replace("lambda h, qi: (h, qi, 0))],",
                            "lambda h, qi: (h, qi))],")
    assert "pallas-kernel-contract" in rules_hit(bad)


def test_pallas_prefetch_grid_spec_arity():
    src = """
        import jax
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call(kern, lens, x, g, hd, t, bk):
            grid = (8, pl.cdiv(t, bk))
            grid_spec = pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[pl.BlockSpec((1, g, hd),
                                       lambda b, ki, lens: (b, 0, 0))],
                out_specs=pl.BlockSpec((1, g, hd),
                                       lambda b, ki, lens: (b, 0, 0)),
            )
            return pl.pallas_call(
                kern, grid_spec=grid_spec,
                out_shape=jax.ShapeDtypeStruct((8, g, hd), x.dtype),
            )(lens, x)
    """
    assert "pallas-kernel-contract" not in rules_hit(src)
    # dropping the prefetch ref from one index_map is an arity bug
    bad = src.replace("lambda b, ki, lens: (b, 0, 0))],",
                      "lambda b, ki: (b, 0, 0))],")
    assert "pallas-kernel-contract" in rules_hit(bad)


# ---------------------------------------------------------------------------
# pallas-blockspec-shape
# ---------------------------------------------------------------------------

SHAPE_OK = """
    import jax
    from jax.experimental import pallas as pl

    def call(kern, x, hd):
        return pl.pallas_call(
            kern,
            grid=(4, 2),
            in_specs=[pl.BlockSpec((1, 4, hd), lambda b, ki: (b, ki, 0))],
            out_specs=pl.BlockSpec((1, 4, hd), lambda b, ki: (b, ki, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 8, hd), x.dtype),
        )(x)
"""


def test_blockspec_shape_consistent_call_not_flagged():
    assert "pallas-blockspec-shape" not in rules_hit(SHAPE_OK)
    assert "pallas-blockspec-shape" not in rules_hit(PALLAS_OK)


def test_blockspec_shape_non_dividing_block_flagged():
    bad = SHAPE_OK.replace("out_specs=pl.BlockSpec((1, 4, hd)",
                           "out_specs=pl.BlockSpec((1, 3, hd)")
    assert "pallas-blockspec-shape" in rules_hit(bad)


def test_blockspec_shape_grid_axis_overruns_blocks_flagged():
    # grid axis 0 runs 0..7 but dim 0 only holds 4 blocks
    bad = SHAPE_OK.replace("grid=(4, 2),", "grid=(8, 2),")
    assert "pallas-blockspec-shape" in rules_hit(bad)


def test_blockspec_shape_constant_index_out_of_symbolic_dim_flagged():
    # block dim == operand dim (same name `hd`) pins the dim to ONE
    # block: a constant index 1 is out of range with no literal around
    bad = SHAPE_OK.replace("lambda b, ki: (b, ki, 0)),\n"
                           "            out_shape",
                           "lambda b, ki: (b, ki, 1)),\n"
                           "            out_shape")
    assert bad != SHAPE_OK
    assert "pallas-blockspec-shape" in rules_hit(bad)


def test_blockspec_shape_negative_index_flagged():
    bad = SHAPE_OK.replace("lambda b, ki: (b, ki, 0)),\n"
                           "            out_shape",
                           "lambda b, ki: (b, ki, -1)),\n"
                           "            out_shape")
    assert bad != SHAPE_OK
    assert "pallas-blockspec-shape" in rules_hit(bad)


def test_blockspec_shape_rank_mismatch_flagged():
    bad = SHAPE_OK.replace("out_specs=pl.BlockSpec((1, 4, hd)",
                           "out_specs=pl.BlockSpec((1, 4)")
    assert "pallas-blockspec-shape" in rules_hit(bad)


# ---------------------------------------------------------------------------
# regression injections into REAL sources (acceptance criteria)
# ---------------------------------------------------------------------------

def test_reintroducing_wall_clock_into_bench_serving_fails():
    src = (ROOT / "benchmarks" / "bench_serving.py").read_text()
    assert "wall-clock-duration" not in {
        f.rule for f in run_source(src, "benchmarks/bench_serving.py").active}
    bad = src.replace("t0 = time.monotonic()", "t0 = time.time()", 1) \
             .replace("time.monotonic() - t0", "time.time() - t0")
    assert bad != src, "expected the monotonic timer to exist"
    assert "wall-clock-duration" in {
        f.rule for f in run_source(bad, "benchmarks/bench_serving.py").active}


def test_reintroducing_set_iteration_into_async_schedule_fails():
    path = "src/repro/dist/async_schedule.py"
    src = (ROOT / path).read_text()
    assert not run_source(src, path).active
    bad = src + textwrap.dedent("""

        def apply_pending(pending):
            out = []
            for key in pending.values():
                out.append(key)
            return out
    """)
    assert "nondeterminism-in-dist" in {
        f.rule for f in run_source(bad, path).active}


def test_planting_wall_clock_into_ingestion_points_fails():
    """The mid-round ingestion bounds are pure virtual time; computing
    them from the wall clock would silently desynchronize the fleet's
    prefixes.  A planted time.time() in the real ingestion code must
    trip the nondeterminism rule."""
    path = "src/repro/dist/async_schedule.py"
    src = (ROOT / path).read_text()
    assert not run_source(src, path).active
    bad = src.replace(
        "t_j = t_begin[p][r] + j * speeds[p]",
        "t_j = t_begin[p][r] + j * speeds[p] + time.time() * 0", 1)
    assert bad != src, "expected the ingestion-point computation to exist"
    assert "nondeterminism-in-dist" in {
        f.rule for f in run_source(bad, path).active}


def test_planting_wall_clock_into_ingest_segment_fails():
    """Same bar for the worker's timed ingestion segment: the monotonic
    segment timers must stay monotonic (time.time() is banned across
    all dist/async_* modules)."""
    path = "src/repro/dist/async_trainer.py"
    src = (ROOT / path).read_text()
    assert not run_source(src, path).active
    bad = src.replace("t_ing = time.monotonic()",
                      "t_ing = time.time()", 1)
    assert bad != src, "expected the ingestion wait segment to exist"
    assert "nondeterminism-in-dist" in {
        f.rule for f in run_source(bad, path).active}


def test_breaking_a_real_kernel_contract_fails():
    path = "src/repro/kernels/flash_attention.py"
    src = (ROOT / path).read_text()
    assert not run_source(src, path).active
    bad = src.replace("lambda h, qi, ki: (h, qi, 0)",
                      "lambda h, qi: (h, qi, 0)", 1)
    assert bad != src
    assert "pallas-kernel-contract" in {
        f.rule for f in run_source(bad, path).active}


def test_stale_block_index_in_ring_kernel_fails():
    """The ring kernel's out block spans the whole head dim (block hd ==
    operand hd -> one block); a stale constant index 1 there must trip
    the shape rule even though every dim is symbolic."""
    path = "src/repro/kernels/decode_attention.py"
    src = (ROOT / path).read_text()
    assert not run_source(src, path).active
    bad = src.replace(
        "out_specs=pl.BlockSpec((1, g, hd),\n"
        "                               lambda r, bi, lens, starts, tabs:"
        " (r, 0, 0)),",
        "out_specs=pl.BlockSpec((1, g, hd),\n"
        "                               lambda r, bi, lens, starts, tabs:"
        " (r, 0, 1)),", 1)
    assert bad != src, "expected the ring kernel's out spec to exist"
    assert "pallas-blockspec-shape" in {
        f.rule for f in run_source(bad, path).active}


def test_stale_block_index_in_flash_kernel_fails():
    path = "src/repro/kernels/flash_attention.py"
    src = (ROOT / path).read_text()
    bad = src.replace("lambda h, qi, ki: (h, qi, 0)),\n"
                      "        out_shape",
                      "lambda h, qi, ki: (h, qi, 1)),\n"
                      "        out_shape", 1)
    assert bad != src, "expected the flash kernel's out spec to exist"
    assert "pallas-blockspec-shape" in {
        f.rule for f in run_source(bad, path).active}


def test_reintroducing_pop0_into_engine_fails():
    path = "src/repro/serve/engine.py"
    src = (ROOT / path).read_text()
    bad = src.replace("self._replay[s].popleft()", "self._replay[s].pop(0)")
    assert bad != src
    assert "quadratic-queue" in {
        f.rule for f in run_source(bad, path).active}


# ---------------------------------------------------------------------------
# pragmas
# ---------------------------------------------------------------------------

def test_trailing_pragma_suppresses_and_is_recorded():
    report = lint("""
        import time
        t0 = time.time()
        w = time.time() - t0  # repro-lint: disable=wall-clock-duration -- why
    """)
    assert "wall-clock-duration" not in {f.rule for f in report.active}
    assert any(f.suppressed_by == "pragma" for f in report.suppressed)


def test_standalone_pragma_above_suppresses():
    report = lint("""
        import time
        t0 = time.time()
        # repro-lint: disable=wall-clock-duration -- continuation reasons
        # may span further comment lines
        w = time.time() - t0
    """)
    assert "wall-clock-duration" not in {f.rule for f in report.active}


def test_pragma_for_wrong_rule_does_not_suppress():
    report = lint("""
        import time
        t0 = time.time()
        w = time.time() - t0  # repro-lint: disable=quadratic-queue -- nope
    """)
    assert "wall-clock-duration" in {f.rule for f in report.active}


def test_file_level_pragma_and_disable_all():
    report = lint("""
        # repro-lint: disable-file=wall-clock-duration -- fixture
        import time
        t0 = time.time()
        w = time.time() - t0
        q = []
        q.insert(0, 1)  # repro-lint: disable=all -- fixture
    """)
    assert not report.active


def test_pragma_reason_parsed():
    pragmas = parse_pragmas(
        "x = 1  # repro-lint: disable=quadratic-queue -- bounded by N\n")
    assert pragmas.pragmas[0].reason == "bounded by N"
    assert pragmas.pragmas[0].rules == ("quadratic-queue",)


def test_multiline_statement_span_pragma():
    # pragma on an inner line of a multi-line offending expression
    report = lint("""
        import time
        t0 = time.time()
        w = (
            time.time()  # repro-lint: disable=wall-clock-duration -- span
            - t0
        )
    """)
    assert "wall-clock-duration" not in {f.rule for f in report.active}


# ---------------------------------------------------------------------------
# baseline round-trip
# ---------------------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    src = textwrap.dedent("""
        import time
        t0 = time.time()
        w = time.time() - t0
    """)
    report = run_source(src, "legacy/old_bench.py")
    assert report.active
    bl = tmp_path / "baseline.json"
    baseline_mod.write(str(bl), report.active)

    entries = baseline_mod.load(str(bl))
    active, matched = baseline_mod.apply(
        run_source(src, "legacy/old_bench.py").active, entries)
    assert not active and len(matched) == len(report.active)

    # a NEW finding (different offending line) is not absorbed
    src2 = src + "w2 = time.time() - t0\n"
    active2, matched2 = baseline_mod.apply(
        run_source(src2, "legacy/old_bench.py").active, entries)
    assert len(active2) == 1 and "w2" in active2[0].snippet

    # fingerprints survive pure line drift (offsets shift, lines intact)
    src3 = "\n\n\n" + src
    active3, _ = baseline_mod.apply(
        run_source(src3, "legacy/old_bench.py").active, entries)
    assert not active3


def test_committed_baseline_is_empty():
    """Repo convention (ISSUE 7): intentional exceptions are pragmas
    with reasons; the committed baseline carries no grandfathered
    findings."""
    data = json.loads((ROOT / ".repro-lint-baseline.json").read_text())
    assert data["findings"] == []


# ---------------------------------------------------------------------------
# the tier-1 gate + CLI
# ---------------------------------------------------------------------------

def test_repo_is_lint_clean():
    """The whole repo passes its own linter (modulo inline pragmas,
    which all carry reasons — asserted below)."""
    report = run_paths([str(ROOT / t) for t in LINT_TREES])
    assert report.files_checked > 50
    assert not report.errors, report.errors
    assert not report.active, "\n" + report.render()


def test_every_repo_pragma_carries_a_reason():
    for tree in LINT_TREES:
        for py in sorted((ROOT / tree).rglob("*.py")):
            if "__pycache__" in py.parts:
                continue
            for pragma in parse_pragmas(py.read_text()).pragmas:
                assert pragma.reason, (
                    f"{py}:{pragma.line}: pragma without a reason "
                    "(use `-- <why>`)")


def _run_cli(*args, cwd=None):
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, cwd=cwd or ROOT,
        env={"PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin"})


def test_cli_check_clean_exits_zero():
    res = _run_cli("--check", *LINT_TREES)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "0 finding(s)" in res.stdout


def test_cli_check_dirty_exits_nonzero_and_json_report(tmp_path):
    bad = tmp_path / "dirty.py"
    bad.write_text("import time\nt0 = time.time()\nw = time.time() - t0\n")
    out = tmp_path / "report.json"
    res = _run_cli("--check", "--json", str(out), str(bad))
    assert res.returncode == 1, res.stdout + res.stderr
    payload = json.loads(out.read_text())
    assert payload["findings"] and payload["files_checked"] == 1
    assert payload["findings"][0]["rule"] == "wall-clock-duration"
    # without --check the same findings exit 0 (report-only mode)
    res2 = _run_cli(str(bad))
    assert res2.returncode == 0


def test_cli_list_rules():
    res = _run_cli("--list-rules")
    assert res.returncode == 0
    for rule in EXPECTED_RULES:
        assert rule in res.stdout


# ---------------------------------------------------------------------------
# --fix: the two mechanical autofixes (wall-clock durations, pop(0))
# ---------------------------------------------------------------------------

from repro.analysis.fixes import fix_source  # noqa: E402


def fix(src):
    new, n = fix_source(textwrap.dedent(src), "fixture.py")
    return new, n


def test_fix_wall_clock_duration_rewrites_both_ends():
    new, n = fix("""
        import time
        def f():
            t0 = time.time()
            work()
            return time.time() - t0
    """)
    assert n == 2
    assert "time.monotonic() - t0" in new
    assert "t0 = time.monotonic()" in new
    assert "time.time()" not in new
    assert not lint(new).active


def test_fix_leaves_bare_timestamps_alone():
    src = """
        import time
        def stamp():
            return {"ts": time.time()}
    """
    new, n = fix(src)
    assert n == 0 and new == textwrap.dedent(src)


def test_fix_pop0_on_deque_receiver_rewrites_method_only():
    new, n = fix("""
        from collections import deque
        q = deque()
        def drain():
            while q:
                item = q.pop(0)
    """)
    assert n == 1
    assert "q.popleft()" in new and "pop(0)" not in new
    assert new.count("deque(") == 1          # ctor untouched


def test_fix_pop0_on_list_receiver_converts_to_deque_with_import():
    new, n = fix("""
        import os
        class S:
            def __init__(self):
                self.queue = []
            def drain(self):
                while self.queue:
                    item = self.queue.pop(0)
            def requeue(self, x):
                self.queue.insert(0, x)
    """)
    assert n == 3        # pop site + insert site + [] ctor
    assert "self.queue.popleft()" in new
    assert "self.queue.appendleft(x)" in new
    assert "self.queue = deque()" in new
    assert "from collections import deque" in new
    # the import lands after the existing imports, once
    assert new.count("from collections import deque") == 1
    assert not lint(new).active


def test_fix_skips_unknown_receiver():
    """A receiver whose initializer the fixer cannot prove rewritable
    must be left alone — breaking a real list is worse than O(n)."""
    src = """
        def drain(q):
            while q:
                item = q.pop(0)
    """
    new, n = fix(src)
    assert n == 0 and new == textwrap.dedent(src)
    assert lint(textwrap.dedent(src)).active   # the finding remains


def test_fix_respects_pragmas():
    src = """
        import time
        def f():
            t0 = time.time()  # repro-lint: disable=wall-clock-duration -- fixture
            return time.time() - t0  # repro-lint: disable=wall-clock-duration -- fixture
    """
    new, n = fix(src)
    assert n == 0 and new == textwrap.dedent(src)


def test_fix_is_idempotent():
    """fix_source on its own output yields zero further edits."""
    first, n1 = fix("""
        import time
        from collections import deque
        class S:
            def __init__(self):
                self.q = []
                self.t0 = time.time()
            def drain(self):
                while self.q:
                    self.q.pop(0)
            def age(self):
                return time.time() - self.t0
    """)
    assert n1 > 0
    second, n2 = fix_source(first, "fixture.py")
    assert n2 == 0 and second == first
    assert not lint(first).active


def test_cli_fix_applies_and_converges(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(textwrap.dedent("""
        import time
        def wait():
            t0 = time.time()
            return time.time() - t0
    """))
    first = _run_cli("--fix", str(tmp_path))
    assert first.returncode == 0 and "2 fix(es)" in first.stdout
    assert "time.monotonic()" in bad.read_text()
    again = _run_cli("--fix", "--check", str(tmp_path))
    assert again.returncode == 0 and "0 fix(es)" in again.stdout
