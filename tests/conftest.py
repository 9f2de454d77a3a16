"""Test harness config.

Per SURVEY.md §4.3 the reference's distributed tests run "multi-node without a
cluster" (CPU Gloo DDP).  The TPU-native analog: run every test on XLA:CPU
with a virtual 8-device mesh so pjit/shard_map paths execute real collectives
without TPU hardware.  JAX reads that environment when it is imported, so if
pytest was started without it we re-exec pytest once with it set.  (Tests that
need an attached chip are marked ``tpu``, start their own process and are not
part of the default run: tests/test_tpu_hw.py.)
"""

import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count=8"


def _want_env() -> dict:
    # preserve any user-supplied XLA_FLAGS, only appending the device-count
    xla = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in xla:
        xla = f"{xla} {_HOST_DEVICES_FLAG}".strip()
    if "xla_backend_optimization_level" not in xla:
        # tests are compile-bound, not FLOP-bound: O0 cuts XLA:CPU compile
        # time ~40% with identical semantics (worker subprocesses inherit it)
        xla = f"{xla} --xla_backend_optimization_level=0".strip()
    return {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": xla,
        "TPU_AIR_NUM_CHIPS": os.environ.get("TPU_AIR_NUM_CHIPS", "8"),
        # persistent XLA compilation cache: many tests (and their worker
        # subprocesses, which inherit the env) compile identical tiny-model
        # steps — cache hits cut the single-core suite time substantially,
        # and repeat runs even more.  The program's own rule
        # (runtime.place_compile_cache): a directory named from outside is
        # left alone, otherwise the one fixed directory in the checkout.
        "JAX_COMPILATION_CACHE_DIR": (
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO, ".jax_cache")
        ),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": os.environ.get(
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5"
        ),
    }


def _needs_reexec() -> bool:
    if os.environ.get("TPU_AIR_TEST_REEXEC") == "1":
        return False
    return any(os.environ.get(k) != v for k, v in _want_env().items())


def pytest_configure(config):
    if not _needs_reexec():
        return
    # pytest's fd-level capture has already replaced fd 1/2 — restore them
    # before exec or the re-exec'd run writes into a dead temp file.
    capman = config.pluginmanager.getplugin("capturemanager")
    if capman is not None:
        try:
            capman.stop_global_capturing()
        except Exception:
            pass
    env = dict(os.environ)
    env.update(_want_env())
    env["TPU_AIR_TEST_REEXEC"] = "1"
    os.execve(sys.executable, [sys.executable, "-m", "pytest", *config.invocation_params.args], env)


sys.path.insert(0, _REPO)

import pytest  # noqa: E402

import tpu_air  # noqa: E402


@pytest.fixture(scope="session")
def air():
    """Session-scoped runtime — mirrors the notebooks' single ray.init()."""
    tpu_air.init(num_cpus=4, num_chips=8)
    yield tpu_air
    tpu_air.shutdown()


@pytest.fixture(scope="module")
def lm_live(lm):
    """The requesting module's tiny causal LM (its ``lm`` fixture) with the
    kernels times eight.  At its initial weights that model repeats the last
    token it was given whatever the context holds (the tied embedding
    outweighs two layers of 0.02-std kernels), so a stream there survives a
    wrong K/V page; with these every token depends on the whole context."""
    import jax
    import numpy as np

    from tpu_air.models.lm.generate import generate

    cfg, model, params = lm
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: x * 8.0 if path[-1].key == "kernel" else x, params)
    ref = np.asarray(generate(model, params, [[7, 3, 9]], max_new_tokens=8,
                              eos_token_id=None))[0].tolist()
    assert len(set(ref)) > 4, ref
    return cfg, model, params
