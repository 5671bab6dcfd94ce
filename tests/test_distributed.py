"""Distributed features: sharding rules, compression, pipeline parallelism.

Multi-device behaviour is verified in subprocesses with forced host devices
(the main test process must keep the single real CPU device).

(The hypothesis-based property tests live in
``test_distributed_properties.py`` so this module collects without the
optional ``hypothesis`` extra.)
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.distributed.compression import (dequantize_int8, ef_compress,
                                           ef_init, quantize_int8)
from repro.distributed.sharding import P, sanitize_spec

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# sharding rule fallbacks
# ---------------------------------------------------------------------------

class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_sanitize_drops_non_dividing_axes():
    mesh = _FakeMesh({"data": 16, "model": 16})
    # batch=1 cannot shard over data -> replicated
    assert sanitize_spec(P(("pod", "data"), None), (1, 128), mesh) == P(None, None)
    # 'pod' absent on single-pod mesh -> silently dropped
    assert sanitize_spec(P(("pod", "data"), None), (32, 128), mesh) == P("data", None)
    # divisible dims keep their axes, missing trailing dims pad with None
    assert sanitize_spec(P("model"), (32, 64, 7), mesh) == P("model", None, None)
    assert sanitize_spec(P(None, "model"), (3, 48), mesh) == P(None, "model")


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_error_feedback_recovers_gradient_sum():
    """Sum of compressed grads -> sum of true grads (EF property)."""
    rng = np.random.default_rng(0)
    grads = [jnp.asarray(rng.normal(size=(32,)), jnp.float32) for _ in range(50)]
    state = ef_init(grads[0])
    total_true = sum(np.asarray(g) for g in grads)
    total_comp = np.zeros(32)
    for g in grads:
        cg, state = ef_compress(g, state)
        total_comp += np.asarray(cg)
    resid = np.abs(total_comp + np.asarray(state) - total_true).max()
    assert resid < 1e-3  # compressed + carried error == exact sum


def test_compression_payload_is_4x_smaller():
    x = jnp.zeros((1024,), jnp.float32)
    q, s = quantize_int8(x)
    assert q.dtype == jnp.int8 and q.nbytes * 4 == x.nbytes


# ---------------------------------------------------------------------------
# pipeline parallelism (subprocess: forced host devices)
# ---------------------------------------------------------------------------

_PIPE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_apply
    mesh = jax.make_mesh((4,), ("pipe",))
    S, M, B, D = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    W = jax.random.normal(key, (S, D, D)) * 0.3
    x = jax.random.normal(jax.random.PRNGKey(1), (M, B, D))
    stage = lambda w, h: jnp.tanh(h @ w)
    got = pipeline_apply(stage, W, x, mesh)
    want = x
    for s in range(S):
        want = jnp.tanh(want @ W[s])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    print("PIPE_OK")
""")

_SPMD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.distributed.sharding import use_mesh, sanitize_tree
    from repro.models.lm import init_lm, spec_lm
    from repro.optim import make_optimizer, opt_state_specs
    from repro.train.steps import TrainHParams, make_train_step

    cfg = dataclasses.replace(get_smoke_config("qwen3-0.6b"), n_layers=2,
                              compute_dtype="float32")
    hp = TrainHParams(remat=False, warmup=1)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    opt_init, _ = make_optimizer(cfg.optimizer)
    opt = opt_init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab)
    batch = {"tokens": tokens}

    # single-device reference
    p1, o1, m1 = jax.jit(make_train_step(cfg, hp))(params, opt, batch)

    # 4x2 (data x model) SPMD; Auto axes: the model code states shardings
    # as constraints and lets GSPMD propagate them
    from jax.sharding import AxisType
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))
    pspec = spec_lm(cfg)
    psh = sanitize_tree(pspec, params, mesh)
    osh = sanitize_tree(opt_state_specs(pspec, params, cfg.optimizer), opt, mesh)
    from jax.sharding import NamedSharding, PartitionSpec as P
    bsh = {"tokens": NamedSharding(mesh, P("data", None))}
    with use_mesh(mesh):
        step = jax.jit(make_train_step(cfg, hp), in_shardings=(psh, osh, bsh),
                       out_shardings=(psh, osh, None))
        p2, o2, m2 = step(jax.device_put(params, psh), jax.device_put(opt, osh),
                          jax.device_put(batch, bsh))
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]), rtol=1e-4)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-3, atol=3e-4)
    print("SPMD_OK")
""")


def _run_sub(script: str, marker: str):
    from conftest import multidevice_emulation_reason

    reason = multidevice_emulation_reason()
    if reason is not None:
        pytest.skip(f"multi-device emulation unavailable: {reason}")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    res = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=600)
    assert marker in res.stdout, f"stdout={res.stdout}\nstderr={res.stderr[-3000:]}"


def test_pipeline_parallel_four_stages_subprocess():
    _run_sub(_PIPE_SCRIPT, "PIPE_OK")


def test_spmd_train_step_matches_single_device_subprocess():
    """FSDP+TP sharded train step == single-device train step (f32)."""
    _run_sub(_SPMD_SCRIPT, "SPMD_OK")
