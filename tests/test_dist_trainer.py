"""Mesh-trainer invariants (run in a subprocess with 8 host devices) and
single-device-safe unit checks."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke, get_train, list_archs
from repro.configs.base import TrainConfig
from repro.dist.sharding import greedy_spec
from repro.dist.trainer import init_train_state
from repro.models import build_model


def test_mesh_trainer_invariants_subprocess():
    script = os.path.join(os.path.dirname(__file__), "dist_check_script.py")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    res = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, timeout=900)
    assert "DIST_CHECK_OK" in res.stdout, res.stdout + res.stderr


PHASES = ("grad", "accumulate", "zsum", "prox", "select", "token",
          "exchange")


def _op_names(hlo, opcode=None):
    """op_name of every instruction of HLO text (of `opcode` only, when
    given)."""
    out = []
    for line in hlo.splitlines():
        if opcode and not re.search(rf" {opcode}\(", line):
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        if m:
            out.append(m.group(1))
    return out


@pytest.mark.parametrize("walks", [1, 2])
def test_superstep_phases_are_named_in_the_compiled_step(walks):
    """Vmapped agents (A=2) on one device: the compiled step's op_name
    metadata carries every apibcd.* phase (with one walk the sum over
    walks compiles to nothing), the kernel sits under apibcd.prox, and
    the layer scans under apibcd.grad and model.blocks."""
    from repro.launch.train import Superstep
    run = Superstep(get_smoke("qwen2-0.5b"), jax.devices()[:1], agents=2,
                    walks=walks, batch_per_agent=2, seq=16, place=False)
    hlo = run.lower(run.abstract_batch()).compile().as_text()
    names = _op_names(hlo)
    found = {m for n in names for m in re.findall(r"apibcd\.(\w+)", n)}
    want = set(PHASES) - ({"zsum"} if walks == 1 else set())
    assert want <= found, found
    # the Pallas kernel (interpreted here: a loop over its grid)
    assert any("/apibcd.prox/prox_update/" in n for n in names)
    loops = _op_names(hlo, "while")
    scans = [n for n in loops if "model.blocks" in n]
    assert len(scans) == 2         # the forward and the backward scan
    assert all(re.search(r"/apibcd\.grad/.*model\.blocks", n)
               for n in scans)
    assert any("transpose(jvp(model.blocks))" in n for n in scans)
    for scope in ("model.embed", "model.head"):
        assert any(f"apibcd.grad/vmap(jvp({scope}))" in n for n in names)


def test_mesh_superstep_exchange_scope_subprocess():
    """Over a 4-device mesh the ring hop is a collective-permute under
    apibcd.exchange."""
    script = os.path.join(os.path.dirname(__file__), "scope_check_script.py")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    res = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert "SCOPE_CHECK_OK" in res.stdout, res.stdout + res.stderr
    found = json.loads(res.stdout.splitlines()[-2])
    assert found["collective-permute"]
    assert all("/apibcd.exchange/" in n for n in found["collective-permute"])
    assert all(re.search(r"/apibcd\.grad/.*model\.blocks", n)
               for n in found["while"])


def test_superstep_step_writes_its_spans_into_a_trace(tmp_path):
    """`Superstep.step` dispatches inside an "apibcd.step" span and its
    own uploads inside "apibcd.batch_upload", read back from a CPU
    profiler trace."""
    from jax.profiler import ProfileData

    from repro.launch.train import Superstep
    run = Superstep(get_smoke("qwen2-0.5b"), jax.devices()[:1], agents=1,
                    walks=1, batch_per_agent=2, seq=8)
    jax.block_until_ready(run.step(0))
    jax.profiler.start_trace(str(tmp_path))
    for i in (1, 2):
        jax.block_until_ready(run.step(i))
    jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    spans = [(e.name, dict(e.stats))
             for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("apibcd.")]
    steps = [st for n, st in spans if n == "apibcd.step"]
    assert [int(st["step_num"]) for st in steps] == [1, 2]
    assert [n for n, _ in spans].count("apibcd.batch_upload") == 2


def test_train_launcher_profile_dir_writes_a_trace(tmp_path):
    """`python -m repro.launch.train --profile-dir` traces the steps
    after the first; the Perfetto copy holds their apibcd.step spans."""
    import gzip
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen2-0.5b",
         "--smoke", "--agents", "1", "--walks", "1", "--steps", "3",
         "--batch-per-agent", "2", "--seq", "8",
         "--profile-dir", str(tmp_path)],
        capture_output=True, text=True, env=env, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr
    (perfetto,) = tmp_path.rglob("perfetto_trace.json.gz")
    events = json.loads(gzip.decompress(perfetto.read_bytes()))
    events = events["traceEvents"] if isinstance(events, dict) else events
    names = [e.get("name") for e in events]
    assert names.count("apibcd.step") == 2


def test_greedy_spec_assigns_divisible_dims():
    from jax.sharding import PartitionSpec as P
    spec = greedy_spec((24, 896, 4864), {"replica": 16, "model": 8},
                       skip_leading=1)
    assert spec == P(None, "model", "replica") or \
        spec == P(None, "replica", "model")
    # whisper's odd vocab falls back
    spec = greedy_spec((51865, 768), {"model": 16})
    assert spec == P(None, "model")
    # nothing divisible -> fully replicated
    spec = greedy_spec((7, 13), {"model": 16, "replica": 6})
    assert spec == P(None, None)


def test_train_state_structure():
    cfg = get_smoke("internlm2-1.8b")
    model = build_model(cfg)
    tcfg = TrainConfig(num_agents=4, model_parallel=1, num_walks=2)
    shapes = init_train_state(model, tcfg)
    assert set(shapes.keys()) == {"params", "token", "zhat", "gacc"}
    for leaf in jax.tree.leaves(shapes["params"]):
        assert leaf.shape[0] == 4          # agent axis
    for leaf in jax.tree.leaves(shapes["zhat"]):
        assert leaf.shape[:2] == (4, 2)    # [A, M, ...]


@pytest.mark.parametrize("arch", list_archs())
def test_train_configs_fit_mesh(arch):
    """Per-arch TrainConfig must tile 256 and 512 devices exactly."""
    t = get_train(arch)
    for total in (256, 512):
        assert total % (t.num_agents * t.model_parallel) == 0, (
            arch, t.num_agents, t.model_parallel, total)
    assert t.num_agents % t.num_walks == 0


def test_checkpoint_roundtrip(tmp_path):
    from repro.checkpoint import load_checkpoint, save_checkpoint
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path / "ckpt"), params, step=7)
    restored, step = load_checkpoint(str(tmp_path / "ckpt"), params)
    assert step == 7
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_token_stream_deterministic_and_learnable():
    from repro.data.tokens import TokenStream
    s1 = TokenStream(512, seed=3)
    s2 = TokenStream(512, seed=3)
    t1, y1 = s1.sample(4, 64)
    t2, y2 = s2.sample(4, 64)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(y1, y2)
    # targets continue the Markov chain often: successor matches > 50%
    succ = s1.successor[t1]
    assert (succ == y1).mean() > 0.5


def test_optimizers_descend():
    from repro.optim import adam, adamw, sgd
    from repro.optim.optimizers import apply_updates

    def loss(p):
        return jnp.sum((p - 3.0) ** 2)

    for opt in (sgd(0.9), adam(), adamw(weight_decay=0.0)):
        p = jnp.zeros(8)
        st = opt.init(p)
        for _ in range(200):
            g = jax.grad(loss)(p)
            upd, st = opt.update(g, st, p, 0.05)
            p = apply_updates(p, upd)
        assert loss(p) < 1e-2, type(opt)
