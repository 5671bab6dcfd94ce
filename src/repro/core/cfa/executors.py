"""Execution backends behind ``repro.cfa.compile`` — one registry, one gate.

Before this module, running a compiled stencil meant picking one of five
hand-wired entry points (the ``CFAPipeline`` sweep variants and the kernel
wrappers), each with its own dimensionality and port-count restrictions
enforced — or not — at a different layer.  Here the same executors are
registered objects with *declared* capabilities, so backend selection, N-D
gating and port-count validation happen in exactly one place
(:func:`check_backend` / :func:`select_backend`).

Registered backends (all return the same payload — the facet-storage dict;
bit-exact across the eager backends, to float rounding where the recurrence
runs in a compiled program, ``wavefront`` and ``pallas``):

* ``reference`` — untiled oracle (``reference_volume``) scattered into facet
  storage; the ground truth everything else is compared against.
* ``sweep``     — the tile-by-tile reference loop of §V (Fig. 13).
* ``wavefront`` — anti-diagonal waves of independent tiles, each wave's plane
  recurrences one compiled program.
* ``pallas``    — wavefront sweep through the Pallas tile-executor kernel
  (``repro.kernels.stencil``); declared 3-D only, the rank ``auto`` picks it
  for — the paper's kernel configuration.
* ``sharded``   — port-mesh wavefront: facet arrays resident on their
  assigned port's device, waves executed via ``shard_map`` (§VII).
* ``dataflow``  — software-pipelined wavefront: fetch, compute and commit of
  consecutive tiles overlap (Fig. 13 DATAFLOW made a schedule; the modeled
  counterpart is ``BurstModel.time(..., overlap=True)``).

Custom backends register through :func:`register_executor`; the autotuner's
cache key folds :func:`capability_fingerprint` in, so decisions re-search
when the executor capability set changes.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Callable, Protocol, runtime_checkable

import jax.numpy as jnp

from .programs import StencilProgram
from .spaces import IterSpace
from .transform import CFAPipeline

__all__ = [
    "BackendError",
    "Executor",
    "ExecutorCaps",
    "EXECUTORS",
    "register_executor",
    "get_executor",
    "available_backends",
    "ineligible_reason",
    "select_backend",
    "check_backend",
    "capability_fingerprint",
    "host_fingerprint",
]


class BackendError(ValueError):
    """A backend cannot execute the requested (program, space, n_ports)."""


@dataclasses.dataclass(frozen=True)
class ExecutorCaps:
    """Declared capabilities of an execution backend.

    ``ndims`` — iteration-space dimensionalities the backend can execute
    (``None`` = any d >= 2, the ``CFAPipeline`` contract).
    ``multiport`` — whether the backend realises an ``n_ports > 1`` facet
    repartition (anything else requires ``n_ports == 1``).
    ``kernels`` — whether the backend drives the Pallas kernels (so callers
    know an ``interpret=`` knob applies).
    ``storages`` — the facet storage disciplines the backend implements
    (``repro.core.cfa.irredundant.STORAGE_MODES``); a backend that does not
    run a discipline must not silently accept it.
    ``overlap`` — whether the backend overlaps fetch/compute/commit
    (Fig. 13 DATAFLOW); sequential backends should be modeled with
    ``BurstModel.time(..., overlap=False)``.
    ``fields`` — whether the backend runs programs with several fields per
    point (``StencilProgram.fields``).
    """

    ndims: tuple[int, ...] | None = None
    multiport: bool = False
    kernels: bool = False
    storages: tuple[str, ...] = ("redundant", "irredundant", "compressed")
    overlap: bool = False
    fields: bool = True
    description: str = ""


@runtime_checkable
class Executor(Protocol):
    """An execution backend: runs a built pipeline over concrete inputs.

    ``execute`` consumes the live-in planes and returns the facet-storage
    dict — the exact payload of ``CFAPipeline.sweep`` — so results from any
    backend compare bit-for-bit.
    """

    name: str
    caps: ExecutorCaps

    def execute(
        self,
        pipeline: CFAPipeline,
        inputs: jnp.ndarray,
        *,
        dtype=jnp.float32,
        n_ports: int = 1,
        **opts,
    ) -> dict[int, jnp.ndarray]: ...


@dataclasses.dataclass(frozen=True)
class _FnExecutor:
    """An Executor wrapping a plain function (the built-in backends).

    ``opts_allowed`` is the backend's call-option surface; anything else is
    rejected loudly — an ignored ``interpret=False`` on a backend that has
    no kernels (or a typo'd option) must not run silently.
    """

    name: str
    caps: ExecutorCaps
    fn: Callable[..., dict[int, jnp.ndarray]]
    opts_allowed: tuple[str, ...] = ()

    def execute(self, pipeline, inputs, *, dtype=jnp.float32, n_ports=1, **opts):
        unknown = sorted(set(opts) - set(self.opts_allowed))
        if unknown:
            raise TypeError(
                f"backend {self.name!r} does not accept option(s) {unknown}; "
                f"allowed: {sorted(self.opts_allowed) or 'none'}"
            )
        return self.fn(pipeline, inputs, dtype=dtype, n_ports=n_ports, **opts)


# --------------------------------------------------------------------------
# Built-in backends
# --------------------------------------------------------------------------


def _reference(pipeline: CFAPipeline, inputs, *, dtype, n_ports=1):
    """Untiled oracle scattered into facet storage.

    ``reference_volume`` computes every plane over the full space; the
    volume's tile blocks are then committed through the very same
    ``copy_out`` the tiled executors use (``copy_out`` only reads the halo
    buffer's interior), so the returned facets are directly comparable."""
    inputs = inputs.astype(dtype)
    V = pipeline.reference_volume(inputs).astype(dtype)
    facets = pipeline.init_facets(dtype)
    facets = pipeline.load_inputs(facets, inputs)
    t = pipeline.tiling.sizes
    interior = pipeline._interior_slices(pipeline.widths)
    for tile in itertools.product(*(range(n) for n in pipeline.num_tiles)):
        block = V[pipeline.program.with_fields(
            tuple(slice(q * ta, (q + 1) * ta) for q, ta in zip(tile, t)),
            slice(None))]
        H = jnp.zeros(pipeline.halo_shape, dtype)
        H = H.at[interior].set(block)
        facets = pipeline.copy_out(facets, tile, H)
    return facets


def _sweep(pipeline: CFAPipeline, inputs, *, dtype, n_ports=1):
    return pipeline._sweep(inputs, dtype)


def _wavefront(pipeline: CFAPipeline, inputs, *, dtype, n_ports=1):
    return pipeline._sweep_wavefront(inputs, dtype, use_kernel=False)


def _pallas(pipeline: CFAPipeline, inputs, *, dtype, n_ports=1,
            interpret: bool | None = None):
    # interpret=None compiles the kernels on a TPU and interprets them on
    # the CPU backend (repro.kernels.resolve_interpret)
    return pipeline._sweep_wavefront(inputs, dtype, use_kernel=True,
                                     interpret=interpret)


def _sharded(pipeline: CFAPipeline, inputs, *, dtype, n_ports=1, **opts):
    return pipeline._sweep_wavefront_sharded(inputs, dtype, n_ports=n_ports,
                                             **opts)


def _dataflow(pipeline: CFAPipeline, inputs, *, dtype, n_ports=1,
              use_kernel: bool = False, interpret: bool | None = None):
    # the kernel path inherits the pallas backend's envelope (3-D spaces,
    # no compressed storage)
    if use_kernel and pipeline.space.ndim != 3:
        raise BackendError(
            "backend 'dataflow' drives the Pallas tile executor only for "
            f"3-D spaces (use_kernel=True), got a {pipeline.space.ndim}-D "
            "space; drop use_kernel for the host path"
        )
    if use_kernel and pipeline.storage == "compressed":
        raise BackendError(
            "backend 'dataflow' cannot drive the Pallas tile executor over "
            "compressed facet storage (no in-kernel decode stage); drop "
            "use_kernel for the host path"
        )
    return pipeline._sweep_dataflow(inputs, dtype, use_kernel=use_kernel,
                                    interpret=interpret)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------

EXECUTORS: dict[str, Executor] = {}


def register_executor(executor: Executor, *, overwrite: bool = False) -> Executor:
    """Register a backend under ``executor.name`` (also usable on custom
    Executor objects from outside this module)."""
    if not overwrite and executor.name in EXECUTORS:
        raise ValueError(f"backend {executor.name!r} is already registered")
    EXECUTORS[executor.name] = executor
    return executor


def get_executor(name: str) -> Executor:
    try:
        return EXECUTORS[name]
    except KeyError:
        raise BackendError(
            f"unknown backend {name!r}; registered: {sorted(EXECUTORS)}"
        ) from None


register_executor(_FnExecutor(
    "reference",
    ExecutorCaps(description="untiled oracle, scattered into facet storage"),
    _reference,
))
register_executor(_FnExecutor(
    "sweep",
    ExecutorCaps(description="tile-by-tile reference loop (paper §V)"),
    _sweep,
))
register_executor(_FnExecutor(
    "wavefront",
    ExecutorCaps(description="batched anti-diagonal tile waves, one "
                             "compiled plane recurrence a wave"),
    _wavefront,
))
register_executor(_FnExecutor(
    "pallas",
    ExecutorCaps(ndims=(3,), kernels=True,
                 # backend="auto" picks pallas on 3-D spaces only, and no
                 # test or cell runs the tile kernel on other ranks or over
                 # compressed storage, so the envelope declares neither
                 storages=("redundant", "irredundant"),
                 description="wavefront sweep through the Pallas tile "
                             "executor (stencil kernel, 3-D only)"),
    _pallas,
    opts_allowed=("interpret",),
))
register_executor(_FnExecutor(
    "sharded",
    # its multi-device copy_in gathers eagerly through the host, one
    # point per value; fields are not carried there
    ExecutorCaps(multiport=True, fields=False,
                 description="port-mesh wavefront via shard_map (§VII)"),
    _sharded,
    opts_allowed=("mesh", "axis", "assignment", "use_kernel", "interpret"),
))
register_executor(_FnExecutor(
    "dataflow",
    ExecutorCaps(kernels=True, overlap=True, fields=False,
                 description="software-pipelined wavefront: fetch/compute/"
                             "commit of consecutive tiles overlap "
                             "(Fig. 13 DATAFLOW)"),
    _dataflow,
    opts_allowed=("use_kernel", "interpret"),
))


# --------------------------------------------------------------------------
# The one gate: capability validation + auto-selection
# --------------------------------------------------------------------------


def _ineligible_reason(
    executor: Executor,
    program: StencilProgram,
    space: IterSpace,
    n_ports: int,
    storage: str = "redundant",
) -> str | None:
    """Why this backend cannot run (program, space, n_ports, storage);
    None if it can."""
    caps = executor.caps
    if caps.ndims is not None and space.ndim not in caps.ndims:
        return (
            f"backend {executor.name!r} executes "
            f"{'/'.join(f'{n}-D' for n in caps.ndims)} spaces only, but "
            f"{program.name!r} @ {space.sizes} is {space.ndim}-D"
        )
    if n_ports > 1 and not caps.multiport:
        return f"backend {executor.name!r} is single-port, got n_ports={n_ports}"
    if program.fields and not caps.fields:
        return (
            f"backend {executor.name!r} runs scalar programs only, but "
            f"{program.name!r} has {program.n_fields} fields {program.fields}"
        )
    if storage not in caps.storages:
        return (
            f"backend {executor.name!r} does not implement "
            f"{storage!r} facet storage (declares {caps.storages})"
        )
    return None


def ineligible_reason(
    executor: Executor,
    program: StencilProgram,
    space: IterSpace,
    n_ports: int = 1,
    storage: str = "redundant",
) -> str | None:
    """Why this backend cannot run (program, space, n_ports, storage);
    ``None`` if it can.  The non-raising form of :func:`check_backend` —
    what the CFA401 contract analysis reports verbatim."""
    return _ineligible_reason(executor, program, space, n_ports, storage)


def check_backend(
    executor: Executor,
    program: StencilProgram,
    space: IterSpace,
    n_ports: int = 1,
    storage: str = "redundant",
) -> None:
    """Validate (program, space, n_ports, storage) against the backend's
    declared capabilities; raises :class:`BackendError` with the eligible
    alternatives spelled out."""
    reason = _ineligible_reason(executor, program, space, n_ports, storage)
    if reason is not None:
        # sorted: the error message must be stable regardless of
        # registration order (matches get_executor's unknown-name error)
        raise BackendError(
            f"{reason}; eligible backends: "
            f"{sorted(available_backends(program, space, n_ports, storage))}"
        )


def available_backends(
    program: StencilProgram, space: IterSpace, n_ports: int = 1,
    storage: str = "redundant",
) -> list[str]:
    """Names of registered backends able to run (program, space, n_ports,
    storage)."""
    return [
        name for name, ex in EXECUTORS.items()
        if _ineligible_reason(ex, program, space, n_ports, storage) is None
    ]


def select_backend(
    program: StencilProgram, space: IterSpace, n_ports: int = 1,
    storage: str = "redundant",
    overlap: bool = False,
) -> str:
    """The ``backend="auto"`` rule, in one place:

    1. ``n_ports > 1``  →  ``sharded``   (the only multiport backend);
    2. ``overlap=True`` →  ``dataflow``  (the only backend that pipelines
       fetch/compute/commit, Fig. 13 DATAFLOW);
    3. 3-D spaces       →  ``pallas``    (the paper's kernel configuration)
       — unless the requested storage discipline is outside the kernel
       backend's declared envelope (compressed), in which case
    4. anything else    →  ``wavefront`` (dimension-generic, batched).
    """
    if n_ports > 1:
        return "sharded"
    if overlap:
        return "dataflow"
    if (space.ndim == 3
            and storage in EXECUTORS["pallas"].caps.storages):
        return "pallas"
    return "wavefront"


def host_fingerprint() -> list[list[str]]:
    """Stable identity of the machine a measurement ran on.

    Folded into the autotune cache key for ``score="measured"`` decisions
    (cache schema v5): a wall-clock ranking measured on one host must not
    be silently reused on another, the exact failure mode the analytic
    model never has.  The jax device is resolved lazily — calling this
    initialises the backend, which measured scoring needs anyway.
    """
    import platform

    import jax

    try:
        dev = jax.devices()[0]
        device = getattr(dev, "device_kind", None) or str(dev)
    except RuntimeError:
        device = "none"
    return [
        ["machine", platform.machine()],
        ["system", platform.system()],
        ["python", platform.python_version()],
        ["jax", jax.__version__],
        ["backend", jax.default_backend()],
        ["device", device],
    ]


def capability_fingerprint() -> list[list]:
    """Stable summary of the registered backend capability set.

    Folded into the autotune cache key (schema v3+): a decision computed
    when e.g. the ``pallas`` backend was 3-D-only must not be silently
    reused after a backend's capability envelope (dimensions, ports,
    storage disciplines) changes.
    """
    return [
        [name, list(ex.caps.ndims) if ex.caps.ndims is not None else None,
         ex.caps.multiport, ex.caps.kernels, list(ex.caps.storages),
         ex.caps.overlap]
        for name, ex in sorted(EXECUTORS.items())
    ]
