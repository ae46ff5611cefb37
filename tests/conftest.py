"""Test harness: force an 8-device virtual CPU mesh before jax import.

Mirrors the reference's test strategy (SURVEY.md §4): elasticity logic runs on
one host against an in-process master + real RPC; collective logic runs on a
virtual multi-device CPU mesh.
"""

import os

# Must be set before any jax import anywhere in the test session.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "0")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual cpu devices, got {len(devs)}"
    return devs


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_cpu_mesh_subprocess(
    code, devices=8, env_extra=None, timeout=300, check=False
):
    """Run a python snippet in a FRESH process with ``devices`` forced
    host CPU devices — the ``--xla_force_host_platform_device_count``
    subprocess pattern from ``test_e2e_elastic``, shared so planner /
    mover / reshard equivalence tests run tier-1 without real TPUs (and
    so crash-site chaos tests can assert on exit codes without taking
    the test runner down with them).

    Returns the ``subprocess.CompletedProcess`` (text mode, output
    captured).  ``env_extra`` overlays the environment — e.g. a
    ``DLROVER_TPU_FAULTS`` plan; without one the variable is scrubbed so
    an operator's ambient chaos plan can't leak into assertions."""
    import subprocess
    import sys as _sys

    env = dict(os.environ)
    env.update(
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": (
                f"--xla_force_host_platform_device_count={devices}"
            ),
            "PYTHONPATH": REPO_ROOT,
        }
    )
    env.pop("DLROVER_TPU_FAULTS", None)
    if env_extra:
        env.update(env_extra)
    proc = subprocess.run(
        [_sys.executable, "-c", code],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO_ROOT,
    )
    if check and proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed rc={proc.returncode}:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}"
        )
    return proc


#: how each of :func:`refusing_calls`' paths names itself in its refusal
REFUSING_PATH_NAMES = {
    "pipeline stage": "the pipeline split", "kv cache": "the KV cache",
    "paged pool": "the paged KV pool",
    "cached decoder": "the cached decoder",
    "hf layout": "the HF Llama layout table",
}


def refusing_calls(cfg):
    """The five paths beside ``llama.forward_hidden`` / ``loss_fn``, each
    as a call that must refuse ``cfg`` (``llama.TRAINING_PATH_ONLY``)
    before it touches a parameter.  A function and not a fixture: its keys
    parametrise tests at collection time."""
    import jax.numpy as jnp
    import numpy as np

    from dlrover_tpu.models import hf_convert, llama_infer, llama_pp

    return {
        "pipeline stage": lambda: llama_pp._stage_fn(cfg),
        "kv cache": lambda: llama_infer.init_cache(cfg, 1, 8),
        "paged pool": lambda: llama_infer.init_paged_pool(cfg, 4, 4),
        "cached decoder": lambda: llama_infer.forward_step(
            None, jnp.zeros((1, 1), jnp.int32), cfg, {"offset": 0}),
        "hf layout": lambda: hf_convert._build_params(
            lambda name: np.zeros(()), lambda: [], cfg, jnp.float32),
    }


@pytest.fixture(scope="session")
def cpu_mesh_subprocess():
    """Session fixture handle on :func:`run_cpu_mesh_subprocess`."""
    return run_cpu_mesh_subprocess
