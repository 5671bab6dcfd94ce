"""The compiled execute: the wavefront executor runs a wave's plane
recurrences as one jitted program a pipeline (``CFAPipeline.execute_wave``).

It must match the eager ``sweep`` oracle to float64 rounding on every
dimensionality, storage discipline and field program the wavefront
executor runs, give the same facets on every sweep of a pipeline, compile
once per distinct wave size, and leave the oracle eager.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro import cfa

# (program, space, tile, storage): 4-D, 2-D, 3-D under compressed storage
# (where auto keeps wavefront), 4-D under irredundant storage, and a field
# program; each has waves of more than one size
CASES = [
    pytest.param(name, space, tile, storage, id=f"{name}-{storage}")
    for name, space, tile, storage in [
        ("heat3d", (8, 8, 8, 8), (4, 4, 4, 4), "redundant"),
        ("heat1d", (8, 12), (4, 4), "redundant"),
        ("jacobi2d5p", (8, 12, 8), (4, 4, 4), "compressed"),
        ("heat3d", (8, 8, 8, 8), (4, 4, 4, 4), "irredundant"),
        ("fdtd2d", (6, 12, 16), (2, 4, 8), "redundant"),
    ]
]


def _pipe(name, space, tile, storage):
    return cfa.compile(name, space, layout=tile, backend="wavefront",
                       storage=storage).pipeline


def _inputs(pipe):
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.normal(size=pipe.program.with_fields(
        (pipe.specs[0].width, *pipe.space.sizes[1:]), pipe.fields)))


@pytest.mark.parametrize("name,space,tile,storage", CASES)
def test_compiled_execute_matches_the_eager_oracle(name, space, tile, storage):
    """Within float64 rounding of ``sweep``, whose recurrence stays eager,
    and bit for bit across two sweeps of one pipeline."""
    pipe = _pipe(name, space, tile, storage)
    x = _inputs(pipe)
    want = pipe._sweep(x, dtype=jnp.float64)
    assert pipe._wave_program is None
    first = pipe._sweep_wavefront(x, dtype=jnp.float64)
    again = pipe._sweep_wavefront(x, dtype=jnp.float64)
    assert set(first) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(first[k]), np.asarray(want[k]),
                                   rtol=1e-12, atol=1e-12)
        assert np.array_equal(np.asarray(first[k]), np.asarray(again[k])), k


@pytest.mark.parametrize("name,space,tile,storage", CASES)
def test_wavefront_sweep_compiles_each_wave_size_once(name, space, tile,
                                                       storage):
    """A whole sweep, then a second one on the same pipeline, leave the
    compiled execute with one entry per distinct wave size."""
    pipe = _pipe(name, space, tile, storage)
    sizes = {len(w) for w in pipe.wavefronts()}
    assert len(sizes) > 1
    pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    program = pipe._wave_program
    assert program._cache_size() == len(sizes)
    pipe._sweep_wavefront(_inputs(pipe), dtype=jnp.float64)
    assert pipe._wave_program is program
    assert program._cache_size() == len(sizes)
