"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracle,
swept over shapes and dtypes as required by the assignment."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.cfa import CFAPipeline, IterSpace, Tiling, get_program
from repro.kernels.stencil import execute_tiles, execute_tiles_ref
from repro.kernels.block_attention import (
    append_token,
    blockify,
    deblockify,
    decode_attention,
    decode_attention_ref,
)
from repro.kernels.ssd import ssd_decode_step, ssd_scan, ssd_scan_ref


# ---------------------------------------------------------------------------
# interpret-mode resolution
# ---------------------------------------------------------------------------

def test_interpret_follows_the_platform(monkeypatch):
    from repro.kernels import resolve_interpret

    assert resolve_interpret() is True  # the CPU backend interprets
    assert resolve_interpret(False) is False  # explicit values are honoured
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_interpret() is False  # a TPU compiles
    assert resolve_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        resolve_interpret()


# ---------------------------------------------------------------------------
# stencil tile executor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["jacobi2d5p", "jacobi2d9p", "jacobi2d9p-gol",
                                  "gaussian", "smith-waterman-3seq"])
@pytest.mark.parametrize("tile,batch", [((4, 8, 8), 3), ((8, 16, 16), 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_stencil_kernel_matches_ref(name, tile, batch, dtype):
    prog = get_program(name)
    w = prog.widths
    hshape = (batch, w[0] + tile[0], w[1] + tile[1], w[2] + tile[2])
    rng = np.random.default_rng(42)
    halos = jnp.asarray(rng.normal(size=hshape), dtype)
    got = execute_tiles(name, halos, tile, interpret=True)
    want = execute_tiles_ref(name, halos, tile)
    tol = 1e-4 if dtype == jnp.float32 else 1e-12
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=tol, atol=tol)


def test_stencil_kernel_agrees_with_pipeline():
    """Kernel path == reference pipeline on a real tiled sweep tile."""
    prog = get_program("jacobi2d5p")
    pipe = CFAPipeline(prog, IterSpace((8, 8, 8)), Tiling((4, 4, 4)))
    rng = np.random.default_rng(0)
    inputs = jnp.asarray(rng.normal(size=(1, 8, 8)), jnp.float32)
    facets = pipe.init_facets(jnp.float32)
    facets = pipe.load_inputs(facets, inputs)
    H = pipe.copy_in(facets, (0, 0, 0))
    want = pipe.execute_tile(H)
    got = execute_tiles("jacobi2d5p", H[None], (4, 4, 4), interpret=True)
    w = prog.widths
    np.testing.assert_allclose(
        np.asarray(got[0]),
        np.asarray(want[w[0]:, w[1]:, w[2]:]),
        rtol=1e-6, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# block (facet-layout) decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Hq,Hkv,D,S,bs", [
    (2, 8, 2, 64, 256, 64),
    (1, 4, 4, 32, 128, 32),   # MHA (no grouping)
    (3, 16, 1, 64, 192, 64),  # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_attention_matches_ref(B, Hq, Hkv, D, S, bs, dtype):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), dtype)
    kc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    vc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), dtype)
    lengths = jnp.asarray(rng.integers(1, S + 1, size=(B,)), jnp.int32)
    got = decode_attention(q, blockify(kc, bs), blockify(vc, bs), lengths)
    want = decode_attention_ref(q, kc, vc, lengths)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_block_attention_partial_final_block():
    """Lengths that do not align with block boundaries must mask correctly."""
    rng = np.random.default_rng(3)
    B, Hq, Hkv, D, S, bs = 2, 4, 2, 32, 128, 32
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(B, S, Hkv, D)), jnp.float32)
    lengths = jnp.asarray([1, 33], jnp.int32)  # deep in first / second block
    got = decode_attention(q, blockify(kc, bs), blockify(vc, bs), lengths)
    want = decode_attention_ref(q, kc, vc, lengths)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


def test_blockify_roundtrip_and_append():
    rng = np.random.default_rng(11)
    B, S, H, D, bs = 2, 64, 4, 16, 16
    kc = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    blocks = blockify(kc, bs)
    np.testing.assert_array_equal(np.asarray(deblockify(blocks)), np.asarray(kc))
    k_new = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    nb2, _ = append_token(blocks, blocks, k_new, k_new, jnp.int32(37))
    back = deblockify(nb2)
    np.testing.assert_array_equal(np.asarray(back[:, 37]), np.asarray(k_new))
    np.testing.assert_array_equal(np.asarray(back[:, :37]), np.asarray(kc[:, :37]))


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,T,H,P,N,chunk", [
    (2, 64, 4, 16, 8, 16),
    (1, 128, 2, 32, 16, 32),
    (2, 96, 8, 8, 4, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_ref(B, T, H, P, N, chunk, dtype):
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(B, T, H, P)), dtype)
    loga = jnp.asarray(-np.abs(rng.normal(size=(B, T, H))) * 0.5, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)) / np.sqrt(N), dtype)
    C = jnp.asarray(rng.normal(size=(B, T, N)) / np.sqrt(N), dtype)
    y, s = ssd_scan(x, loga, Bm, C, chunk=chunk)
    y_ref, s_ref = ssd_scan_ref(x, loga, Bm, C)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(y_ref, np.float32), rtol=tol, atol=tol
    )
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-4, atol=1e-4)


def test_ssd_chunk_invariance():
    """The facet decomposition must be invariant to the chunk size."""
    rng = np.random.default_rng(9)
    B, T, H, P, N = 1, 64, 2, 8, 4
    x = jnp.asarray(rng.normal(size=(B, T, H, P)), jnp.float32)
    loga = jnp.asarray(-np.abs(rng.normal(size=(B, T, H))) * 0.3, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    y8, s8 = ssd_scan(x, loga, Bm, C, chunk=8)
    y64, s64 = ssd_scan(x, loga, Bm, C, chunk=64)
    np.testing.assert_allclose(np.asarray(y8), np.asarray(y64), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s8), np.asarray(s64), rtol=2e-5, atol=2e-5)


def test_ssd_decode_step_consistent_with_scan():
    """Token-by-token decode must follow the scan trajectory."""
    rng = np.random.default_rng(13)
    B, T, H, P, N = 2, 16, 2, 8, 4
    x = jnp.asarray(rng.normal(size=(B, T, H, P)), jnp.float32)
    loga = jnp.asarray(-np.abs(rng.normal(size=(B, T, H))) * 0.3, jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
    y_ref, s_ref = ssd_scan_ref(x, loga, Bm, C)
    S = jnp.zeros((B, H, P, N), jnp.float32)
    for t in range(T):
        y_t, S = ssd_decode_step(S, x[:, t], loga[:, t], Bm[:, t], C[:, t])
        np.testing.assert_allclose(
            np.asarray(y_t), np.asarray(y_ref[:, t]), rtol=1e-5, atol=1e-5
        )
    np.testing.assert_allclose(np.asarray(S), np.asarray(s_ref), rtol=1e-5, atol=1e-5)
