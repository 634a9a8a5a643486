"""Test config: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's multi-virtual-device-in-one-process testing strategy
(SURVEY.md §4) but with real SPMD on fake devices.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
  os.environ["XLA_FLAGS"] = (
      flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# One persistent compile cache for tests, trainer and chip_smoke: a
# cold tier-1 run sits at the edge of its time cap, a warm one does not.
from lingvo_tpu.core import compile_cache  # noqa: E402  (env set first)

compile_cache.Configure()

# -- chip_smoke.py --tiny, beside the run -------------------------------------
# The rehearsal of chip_smoke.py traces the trainer and the serving engine
# end to end: half a minute, nearly all of it under the interpreter lock.
# Tier-1 stops at a time cap it already does not fit, so the rehearsal runs
# as a child process from the end of collection, on cores the test process
# leaves idle, and tests/test_chip_compile.py only reads what it printed.

import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TINY_SMOKE = pytest.StashKey()


def pytest_collection_finish(session):
  if session.config.option.collectonly or not any(
      "tiny_smoke" in getattr(item, "fixturenames", ())
      for item in session.items):
    return
  out, err = tempfile.TemporaryFile("w+"), tempfile.TemporaryFile("w+")
  # its compiles stay out of the cache this process is reading and writing
  child = subprocess.Popen(
      [sys.executable, os.path.join(_ROOT, "chip_smoke.py"), "--tiny"],
      stdout=out, stderr=err, cwd=_ROOT,
      env=dict(os.environ, JAX_ENABLE_COMPILATION_CACHE="false"))
  session.config.stash[_TINY_SMOKE] = (child, out, err)


def pytest_unconfigure(config):
  child, out, err = config.stash.get(_TINY_SMOKE, (None, None, None))
  if child is not None:
    if child.poll() is None:   # its tests never ran: deselected, -x, ctrl-c
      child.kill()
      child.wait()
    out.close()
    err.close()


@pytest.fixture(scope="session")
def tiny_smoke(request):
  """(return code, stdout, stderr) of the `chip_smoke.py --tiny` child."""
  child, out, err = request.config.stash[_TINY_SMOKE]
  returncode = child.wait(timeout=600)
  out.seek(0)
  err.seek(0)
  return returncode, out.read(), err.read()


# -- shared tiny LMs (session-scoped) -----------------------------------------
# One instantiation of each tiny model serves EVERY serving-stack test
# module (test_serving_engine / test_spec_decode / test_ragged_step /
# test_tree_spec): theta init and jit warm-up are the dominant fixture
# cost, and hoisting them session-wide is what keeps the suite inside the
# verify budget as the serving matrix grows.


def TinyLmParams(every_n=None, num_layers=2, use_repeat=False, **overrides):
  """The stack-under-test: 2-layer rotary TransformerLm, vocab 64.

  every_n switches attention mixers for GatedSSMLayer every n layers
  (0 = pure O(1)-state stack, the only shape ModelDraft accepts)."""
  from lingvo_tpu.core import ssm
  from lingvo_tpu.models.lm import layers as lm_layers
  p = lm_layers.TransformerLm.Params().Set(
      name="lm", vocab_size=64, model_dim=32, num_layers=num_layers,
      num_heads=2, hidden_dim=64, use_rotary=True)
  if every_n is not None:
    p = p.Set(use_repeat_layer=use_repeat,
              mixer_tpl=ssm.GatedSSMLayer.Params().Set(state_dim=8,
                                                       chunk_size=4),
              mixer_atten_every_n=every_n)
  return p.Set(**overrides)


def InstantiateLm(p, seed=0):
  import jax
  task = p.Instantiate()
  task.FinalizePaths()
  theta = task.InstantiateVariables(jax.random.PRNGKey(seed))
  return task, theta


@pytest.fixture(scope="session")
def tiny_lm():
  return InstantiateLm(TinyLmParams())


@pytest.fixture(scope="session")
def tiny_lm_swapped(tiny_lm):
  # the same task with a different checkpoint — the "new theta" of hot
  # UpdateTheta swap tests. Session-scoped so its id is stable for the
  # _GreedyRef memo key in test_serving_engine.
  import jax
  task, _ = tiny_lm
  return task, task.InstantiateVariables(jax.random.PRNGKey(7))


@pytest.fixture(scope="session")
def hybrid_lm():
  # flat (non-repeat) stack so a 1-layer early-exit prefix is legal; the
  # repeat-stack prefix path gets its own engine tests
  return InstantiateLm(TinyLmParams(every_n=2, use_repeat=False))


@pytest.fixture(scope="session")
def ssm_draft_lm():
  # pure O(1)-state stack: the only shape ModelDraft accepts (pageless)
  return InstantiateLm(TinyLmParams(every_n=0), seed=1)
