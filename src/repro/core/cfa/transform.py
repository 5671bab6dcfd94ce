"""The CFA "compiler pass" output: a read -> execute -> write tile pipeline.

Mirrors §V of the paper.  Given a :class:`StencilProgram` (post-skew normal
form), a rectangular space and a tiling, :class:`CFAPipeline` provides

* ``init_facets``  — allocate the facet arrays (plus one virtual leading
  block row on the time facet holding live-in planes),
* ``copy_in``      — gather a tile's flow-in from facets into a local halo
  buffer (the on-chip scratchpad; off-chip side reads facet blocks),
* ``execute_tile`` — run the tile's plane recurrence on the halo buffer,
* ``copy_out``     — write the tile's facet blocks (full-tile contiguity:
  each is one contiguous store), one compiled program a tile over the
  pipeline's :class:`CommitPlan`,
* ``_sweep``       — the whole accelerator loop over tiles in lexicographic
  order (the legal schedule under backward dependences); the executor
  registry (``repro.core.cfa.executors``) is the public way to run it.

On real hardware the three phases run as a coarse-grain pipeline
(paper Fig. 13, DATAFLOW); in Pallas the same overlap comes for free from
grid pipelining — see ``repro.kernels.stencil``.  The executors here
still dispatch tile by tile from the host.  The fetch is one compiled
program a tile: ``copy_in`` reads the tile's row of the pipeline's
:class:`FetchPlan`, gather and scatter tables resolved once and kept on
the device.  The wavefront executor runs a wave's plane recurrences as
one compiled program (``execute_wave``); the ``sweep`` oracle and the
dataflow host path keep the eager ``execute_tile``.  The commit is one
compiled program a tile too: ``copy_out`` reads the tile's row of the
pipeline's :class:`CommitPlan`, block indices and permutations resolved
once and kept on the device, and updates the donated facet arrays in
place; ``load_inputs`` stores the live-in row through the same plan.

The pipeline is dimension-generic (the paper's construction is, §IV-F..J):
any d >= 2 works — one time axis plus d-1 spatial axes — so 2-D programs
(``heat1d``), the 3-D Table I suite, and 4-D programs (``heat3d``, the
§IV-J regime) all run through the same code path.

Field programs (``StencilProgram.fields``, e.g. ``fdtd2d``) carry a field
axis of size F right after time in everything a tile touches: live-in
planes ``(w0, F, N_1, ..)``, halo buffers ``(w0+t0, F, w1+t1, ..)`` and
facet arrays (before their inner dims, see ``repro.core.cfa.facets``).
Every table and map here is built per point and spread over the fields
(:meth:`~repro.core.cfa.facets.FacetSpec.spread_fields`); a scalar program has no
field axis, and its shapes and tables are the same as before fields.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
import math
import typing
import warnings
from typing import Mapping

import numpy as np
import jax
import jax.numpy as jnp

from . import obs
from .facets import FacetSpec, build_facet_specs, row_major_strides
from .programs import StencilProgram
from .spaces import IterSpace, Tiling, box_points

__all__ = ["CFAPipeline", "CommitPlan", "FetchPlan"]


def device_index(offsets: np.ndarray) -> jnp.ndarray:
    """Upload host-computed flat gather offsets in the index dtype JAX uses
    (int32 unless x64 is enabled, as it is not on the chip).

    ``jnp.asarray`` silently narrows an int64 offset that does not fit,
    and the gather then reads another element; a facet array past
    2**31 elements would return wrong values silently.  Such an offset
    raises here instead.
    """
    dtype = jax.dtypes.canonicalize_dtype(np.int64)
    if offsets.size and int(offsets.max()) > np.iinfo(dtype).max:
        raise OverflowError(
            f"gather offset {int(offsets.max())} does not fit JAX's index "
            f"dtype {np.dtype(dtype).name}; the facet array is too large to "
            "address without 64-bit indices (jax_enable_x64)"
        )
    return jnp.asarray(offsets, dtype)


@dataclasses.dataclass(frozen=True)
class FetchPlan:
    """Every tile's halo gather of one pipeline, resolved once.

    ``maps[tile]`` is the tile's resolved halo (``CFAPipeline._halo_maps``)
    and ``rows[tile]`` its row in the tables.  ``src[i]`` and ``dst[i]``
    are ``(n_tiles, max_points)`` tables for facet array ``keys[i]``: row
    ``r`` holds the flat facet offsets tile ``r`` reads and the flat
    offsets in the ``shape`` halo buffer they land at.  A short row is
    padded with source 0 and destination ``prod(shape)``, one past the
    buffer's end, which the scatter drops.
    """

    maps: Mapping[tuple[int, ...], Mapping]
    rows: Mapping[tuple[int, ...], object]
    keys: tuple[int, ...]
    src: tuple
    dst: tuple
    shape: tuple[int, ...]


@dataclasses.dataclass(frozen=True)
class CommitPlan:
    """Every tile's facet commit of one pipeline, resolved once.

    ``index[i]`` and ``perm[i]`` are the tables of facet array
    ``keys[i]``: row ``r`` holds the outer index of the block that row
    writes (``(n_rows, n_outer)``) and the permutation along the facet's
    axis that puts the slab in the modulo labelling (``(n_rows, w)``).
    ``rows[tile]`` is a tile's row in every table.  Facet_0's tables
    carry one row more per block of its virtual live-in row, after the
    tiles' rows, in the order :meth:`CFAPipeline._live_in_blocks` gives.

    ``commit`` and ``load`` are the plan's two programs (``None`` in the
    host tables of :meth:`CFAPipeline.commit_tables`): one tile's commit
    over ``(facets, index, perm, row, H)`` and the whole live-in row over
    ``(facet_0, index[0], perm[0], inputs)``, each jitted once per
    pipeline with the facet arrays donated.
    """

    rows: Mapping[tuple[int, ...], object]
    keys: tuple[int, ...]
    index: tuple
    perm: tuple
    commit: typing.Callable | None = None
    load: typing.Callable | None = None


def _pad_rows(pieces: list[list[np.ndarray]], fill: int) -> np.ndarray:
    """One table row per tile, its pieces end to end; short rows padded."""
    rows = [np.concatenate(p) if p else np.zeros(0, np.int64) for p in pieces]
    out = np.full((len(rows), max(len(r) for r in rows)), fill, np.int64)
    for r, row in enumerate(rows):
        out[r, :len(row)] = row
    return out


def _on_one_device(facets: Mapping[int, jnp.ndarray]) -> bool:
    devices = set()
    for arr in facets.values():
        devices.update(arr.devices() if hasattr(arr, "devices") else ())
    return len(devices) <= 1


def _unravel(flat, shape: tuple[int, ...]) -> tuple:
    """Row-major multi-index of flat offsets into an array of ``shape``;
    the leading index is not wrapped, so an offset past the end stays
    out of bounds."""
    idx = []
    for n in reversed(shape[1:]):
        idx.append(flat % n)
        flat = flat // n
    return (flat, *reversed(idx))


@functools.partial(jax.jit, static_argnames=("shape",))
def _fetch_halo(facets, src, dst, row, *, shape):
    """One tile's halo buffer from the fetch plan's tables: per facet
    array, gather the tile's row of source offsets and scatter the values
    to its row of destinations in a zero buffer; pads are dropped.

    The gather indexes each facet in its own shape: a flat view of a
    facet would make the TPU relayout the whole array first (its minor
    dims are tiled and padded), once per tile."""
    H = jnp.zeros(math.prod(shape), facets[0].dtype)
    for f, s, d in zip(facets, src, dst):
        vals = f.at[_unravel(s[row], f.shape)].get(
            mode="promise_in_bounds", wrap_negative_indices=False)
        H = H.at[d[row]].set(vals, mode="drop", wrap_negative_indices=False)
    return H.reshape(shape)


@dataclasses.dataclass
class CFAPipeline:
    #: facet storage discipline this pipeline realises; the irredundant /
    #: compressed variants live in ``repro.core.cfa.irredundant``
    storage: typing.ClassVar[str] = "redundant"

    program: StencilProgram
    space: IterSpace
    tiling: Tiling
    # layout knobs (see repro.core.cfa.facets); defaults = the paper's layout
    ext_dirs: Mapping[int, int] | tuple[tuple[int, int], ...] | None = None
    contiguity: str = "intra-tile"
    # the autotuner decision this pipeline was built from, if any
    decision: object | None = dataclasses.field(default=None, repr=False, compare=False)
    # the compile-time facet->port split (the port_repartition pass); the
    # sharded sweep prefers it over re-deriving one from the decision
    port_assignment: object | None = dataclasses.field(default=None, repr=False, compare=False)
    # runtime telemetry (repro.core.cfa.obs.TraceRecorder); None = tracing
    # off: each phase then only opens its profiler annotation (obs.phase)
    # and allocates no span
    recorder: object | None = dataclasses.field(default=None, repr=False, compare=False)
    specs: Mapping[int, FacetSpec] = dataclasses.field(init=False)
    num_tiles: tuple[int, ...] = dataclasses.field(init=False)
    # the compiled fetch's tables, built on the first single-device copy_in
    _fetch_plan: "FetchPlan | None" = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    # the compiled commit's tables and programs, built on the first
    # single-device load_inputs or copy_out
    _commit_plan: "CommitPlan | None" = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    # the compiled execute, built on the first execute_wave
    _wave_program: typing.Callable | None = dataclasses.field(
        default=None, init=False, repr=False, compare=False)

    def __post_init__(self) -> None:
        if self.space.ndim < 2:
            raise ValueError(
                "the executor needs a time axis plus at least one spatial "
                f"axis (d >= 2); got a {self.space.ndim}-D space"
            )
        if self.program.ndim != self.space.ndim:
            raise ValueError(
                f"program {self.program.name!r} is {self.program.ndim}-D but "
                f"the space is {self.space.ndim}-D"
            )
        self.specs = build_facet_specs(
            self.space, self.program.deps, self.tiling,
            ext_dirs=dict(self.ext_dirs) if self.ext_dirs is not None else None,
            contiguity=self.contiguity,
        )
        self.num_tiles = self.tiling.num_tiles(self.space)
        if 0 not in self.specs:
            raise ValueError("time axis must carry a facet (w_0 >= 1)")

    # -- storage -----------------------------------------------------------

    @property
    def fields(self) -> int:
        """Values a point holds (``StencilProgram.n_fields``)."""
        return self.program.n_fields

    def _array_axis(self, a: int) -> int:
        """Axis of canonical axis ``a`` in a plane stack or halo buffer,
        which carry the field axis after time."""
        return a + 1 if self.program.fields and a > 0 else a

    def facet_shape(self, k: int) -> tuple[int, ...]:
        shape = list(self.specs[k].shape)
        if k == 0:
            shape[0] += 1  # virtual leading block row for live-in planes
        return tuple(shape)

    def init_facets(self, dtype=jnp.float32) -> dict[int, jnp.ndarray]:
        return {k: jnp.zeros(self.facet_shape(k), dtype) for k in self.specs}

    def load_inputs(
        self, facets: dict[int, jnp.ndarray], inputs: jnp.ndarray
    ) -> dict[int, jnp.ndarray]:
        """Pack live-in planes (w_0, [F,] N_1, .., N_{d-1}) into the virtual
        facet_0 row.

        Facets on one device take the :class:`CommitPlan`'s ``load``
        program: every live-in block in one program, facet_0 donated, so
        the facet_0 passed in is deleted.  Facets over several devices
        store block by block through the eager :meth:`_store_block`."""
        want = self.program.with_fields(
            (self.specs[0].width, *self.space.sizes[1:]), self.fields)
        if inputs.shape != want:
            raise ValueError(f"inputs must be {want}")
        facets = dict(facets)
        if _on_one_device(facets):
            plan = self._commit_plan or self._build_commit_plan()
            facets[0] = plan.load(facets[0], plan.index[0], plan.perm[0],
                                  inputs)
            return facets
        for q, sl in self._live_in_blocks():
            facets[0] = self._store_block(facets[0], self.specs[0], (-1, *q),
                                          inputs[sl])
        return facets

    def _live_in_blocks(self):
        """Each block of facet_0's virtual row: its spatial tile index and
        its slice of the live-in planes (every field)."""
        t = self.tiling.sizes
        for q in itertools.product(*(range(n) for n in self.num_tiles[1:])):
            sl = tuple(slice(qa * ta, (qa + 1) * ta) for qa, ta in zip(q, t[1:]))
            yield q, self.program.with_fields((slice(None), *sl), slice(None))

    # -- block addressing ----------------------------------------------------

    def _block_index(self, spec: FacetSpec, tile: tuple[int, ...]) -> tuple[int, ...]:
        """Outer index of the tile's block in facet ``spec``; facet_0's
        blocks sit one row down, below the virtual live-in row (tile
        ``(-1, *q)``)."""
        return tuple(tile[a] + (spec.axis == 0 and a == 0)
                     for a in spec.outer_axes)

    def _block_perm(self, spec: FacetSpec, tile: tuple[int, ...]) -> np.ndarray:
        """The paper's modulo labelling of the block (tile-dependent, in
        general): block position ``m`` along ``spec.axis`` holds slab
        position ``perm[m]``.  The live-in tile ``(-1, *q)`` starts at
        ``-w``."""
        k, w, t_k = spec.axis, spec.width, spec.tile_sizes[spec.axis]
        x0 = tile[k] * t_k + t_k - w
        return np.argsort([(x0 + j) % w for j in range(w)])

    def _store_block(self, arr, spec: FacetSpec, tile, slab):
        """Store one slab, eagerly: the block index and permutation are
        resolved on the host and uploaded with the call.  The commit of
        facets over several devices, and the oracle of the compiled one."""
        return self._put_block(arr, spec, self._block_index(spec, tile),
                               jnp.asarray(self._block_perm(spec, tile)), slab)

    def _put_block(self, arr, spec: FacetSpec, idx, perm, slab):
        """``slab`` has canonical axis order (the field axis after time)
        with axis ``spec.axis`` of size w indexed by slab position; store it
        permuted by ``perm`` and transposed to the facet block layout at
        outer index ``idx``.  Traceable: the compiled commit calls it with
        its tables' rows, :meth:`_store_block` with host values."""
        slab = jnp.take(slab, perm, axis=self._array_axis(spec.axis))
        order = [self._array_axis(a) for a in spec.inner_axes]
        block = slab.transpose([1, *order] if self.program.fields else order)
        return self._commit_block(arr, idx, block, spec)

    def _commit_block(self, arr, idx, block, spec: FacetSpec):
        """Write one laid-out facet block at its outer index: one
        contiguous ``dynamic_update_slice``.  The storage disciplines
        override only this commit step (owner-masked under irredundant
        storage, codec round-trip under compressed — see
        ``repro.core.cfa.irredundant``); it is traced inside the compiled
        commit, so an override uses ``jnp`` and ``lax`` only."""
        block = block.astype(arr.dtype).reshape((1,) * len(idx) + block.shape)
        return jax.lax.dynamic_update_slice(arr, block,
                                            (*idx, *(0,) * (block.ndim - len(idx))))

    # -- copy-in -------------------------------------------------------------

    def _halo_maps(self, tile: tuple[int, ...]):
        """Static gather maps: halo point -> (facet id, flat offset).

        Halo = points of [lo - w, hi) with some coordinate below lo.  Points
        with x_0 < 0 come from the virtual live-in row; points outside the
        space elsewhere keep the zero boundary value.
        """
        d = self.space.ndim
        w = np.array([self.specs[a].width if a in self.specs else 0 for a in range(d)])
        lo = np.array(tile) * np.array(self.tiling.sizes)
        hi = lo + np.array(self.tiling.sizes)
        pts = box_points(lo - w, hi)
        below = (pts < lo).any(axis=1)
        pts = pts[below]
        # spatially out-of-space points are zero-boundary; x_0 < 0 is live-in
        in_space = np.ones(len(pts), dtype=bool)
        for a in range(1, d):
            in_space &= (pts[:, a] >= 0) & (pts[:, a] < self.space.sizes[a])
        in_space &= pts[:, 0] < self.space.sizes[0]
        pts = pts[in_space]
        maps = {}
        taken = np.zeros(len(pts), dtype=bool)
        # virtual live-in reads
        virt = pts[:, 0] < 0
        if virt.any():
            maps["virtual"] = pts[virt]
            taken |= virt
        maps.update(self._halo_hosts(pts, lo, taken))
        if not bool(taken.all()):
            raise AssertionError("halo point not covered by any facet — layout bug")
        return maps, lo, w

    def _halo_hosts(self, pts, lo, taken):
        """Assign each non-virtual halo point to the facet it is read from:
        under redundant storage, the first facet crossed along its own axis
        whose domain contains the point (any copy is valid — they are all
        written).  ``taken`` is updated in place.  The irredundant pipeline
        overrides this with the owner-facet indirection."""
        maps = {}
        for k, spec in self.specs.items():
            mask = ~taken & (pts[:, k] < lo[k]) & (pts[:, k] >= 0) & spec.domain_mask(pts)
            if mask.any():
                maps[k] = pts[mask]
                taken |= mask
        return maps

    def copy_in(self, facets: dict[int, jnp.ndarray], tile: tuple[int, ...]) -> jnp.ndarray:
        """Gather the tile's flow-in into a halo buffer of shape (w + t).

        Facets on one device take the compiled fetch: the pipeline's
        :class:`FetchPlan`, built on the first such call, holds every
        tile's halo maps and device-resident gather/scatter tables, and one
        jitted program per pipeline reads the tile's row of them.  Facets
        over several devices (port-resident facets under the ``sharded``
        executor) take the eager, piece-by-piece gather of
        :meth:`_gather_halo`.  Where the facets sit is the only choice.
        """
        rec = self.recorder
        compiled = _on_one_device(facets)
        with obs.phase(rec, "copy_in", "fetch", fields=self.fields,
                       after=lambda: rec.record_read(self, tile)):
            with obs.phase(rec, "halo_resolve", "fetch",
                           after=lambda: dict(tile=list(tile),
                                              wave=int(sum(tile)),
                                              port=rec.port,
                                              **rec.record_halo(self, maps))):
                if compiled:
                    plan = self._fetch_plan or self._build_fetch_plan()
                    maps = plan.maps[tile]
                else:
                    maps, lo, w = self._halo_maps(tile)
            if rec is not None:
                rec.counters.add("fetch_compiled" if compiled else "fetch_eager", 1)
            if compiled:
                return _fetch_halo(tuple(facets[k] for k in plan.keys),
                                   plan.src, plan.dst, plan.rows[tile],
                                   shape=plan.shape)
            return self._gather_halo(facets, maps, lo, w)

    def _source_offsets(self, key, pts: np.ndarray) -> np.ndarray:
        """Flat offsets of resolved halo points into the facet array they
        are read from: ``facets[key]``, or facet_0's virtual live-in row
        for ``key == "virtual"``; every field's, field after field."""
        if key == "virtual":
            return self._virtual_offsets(pts)
        spec = self.specs[key]
        offs = spec.offsets(pts)
        if key == 0:  # account for the virtual leading row
            offs = offs + spec.block_elems * math.prod(
                spec.num_tiles[a] for a in spec.outer_axes[1:]
            )
        return offs

    def _virtual_offsets(self, pts: np.ndarray) -> np.ndarray:
        """Flat facet_0 offsets of live-in points (x_0 < 0), which sit in
        its virtual row; every field's, field after field."""
        spec = self.specs[0]
        w = spec.width
        idx_cols = []
        for a in spec.outer_axes:
            idx_cols.append(
                np.zeros(len(pts), np.int64) if a == 0 else pts[:, a] // spec.tile_sizes[a]
            )
        for a in spec.inner_axes:
            if a == 0:
                idx_cols.append(pts[:, 0] % w)  # matches the store perm for x0=-w..-1
            else:
                idx_cols.append(pts[:, a] % spec.tile_sizes[a])
        shape = (spec.point_shape[0] + 1, *spec.point_shape[1:])
        return spec.spread_fields(np.stack(idx_cols, axis=1) @ row_major_strides(shape))

    @property
    def halo_shape(self) -> tuple[int, ...]:
        """A tile's halo buffer: (w + t) per axis, the field axis after
        time."""
        return self.program.with_fields(
            tuple(wa + ta for wa, ta in zip(self.widths, self.tiling.sizes)),
            self.fields)

    def _halo_index(self, pts: np.ndarray, lo, w) -> np.ndarray:
        """Multi-indices into the halo buffer of every field of halo points,
        field after field, as :meth:`_source_offsets` orders them."""
        local = pts - (lo - w)
        if not self.program.fields:
            return local
        return np.concatenate([
            np.insert(local, 1, f, axis=1) for f in range(self.fields)])

    def _gather_halo(self, facets: dict[int, jnp.ndarray], maps, lo, w) -> jnp.ndarray:
        """Read the resolved halo points from the facets into a fresh
        (w + t) halo buffer, one eager gather and scatter per piece."""
        pieces = []
        for key, pts in maps.items():
            flat = facets[0 if key == "virtual" else key].reshape(-1)
            vals = flat[device_index(self._source_offsets(key, pts))]
            pieces.append((self._halo_index(pts, lo, w), vals))
        if not _on_one_device(facets):
            H = np.zeros(self.halo_shape, dtype=np.dtype(facets[0].dtype))
            for local, vals in pieces:
                H[tuple(local.T)] = np.asarray(vals)
            H = jnp.asarray(H)
        else:
            H = jnp.zeros(self.halo_shape, facets[0].dtype)
            for local, vals in pieces:
                H = H.at[tuple(jnp.asarray(local.T))].set(vals)
        return H

    # -- compiled fetch --------------------------------------------------------

    def fetch_tables(self) -> "FetchPlan":
        """Resolve every tile's halo once, on the host: the
        :class:`FetchPlan` with numpy tables and each tile's row number.

        The facet shapes, the storage discipline (:meth:`_halo_hosts`) and
        the tile set are fixed for a pipeline instance, so the plan is
        too.  Live-in points go into facet_0's table, beside its real
        rows."""
        tiles = list(itertools.product(*(range(n) for n in self.num_tiles)))
        shape = self.halo_shape
        strides = row_major_strides(shape)
        maps_of = {}
        # per facet array, per tile: the pieces of its source and
        # destination offsets
        src: dict[int, list[list[np.ndarray]]] = {k: [] for k in self.specs}
        dst: dict[int, list[list[np.ndarray]]] = {k: [] for k in self.specs}
        for tile in tiles:
            maps, lo, w = self._halo_maps(tile)
            maps_of[tile] = maps
            for k in self.specs:
                src[k].append([])
                dst[k].append([])
            for key, pts in maps.items():
                k = 0 if key == "virtual" else key
                src[k][-1].append(self._source_offsets(key, pts))
                dst[k][-1].append(self._halo_index(pts, lo, w) @ strides)
        keys = tuple(k for k in self.specs if any(src[k]))
        return FetchPlan(
            maps=maps_of, rows={tile: r for r, tile in enumerate(tiles)},
            keys=keys, shape=shape,
            src=tuple(_pad_rows(src[k], 0) for k in keys),
            dst=tuple(_pad_rows(dst[k], math.prod(shape)) for k in keys),
        )

    def _build_fetch_plan(self) -> "FetchPlan":
        """Build this pipeline's fetch plan and upload its tables and row
        numbers, each once, through :func:`device_index`."""
        plan = self.fetch_tables()
        rows = list(device_index(np.arange(len(plan.rows))))
        self._fetch_plan = dataclasses.replace(
            plan,
            rows={tile: rows[r] for tile, r in plan.rows.items()},
            src=tuple(device_index(s) for s in plan.src),
            dst=tuple(device_index(d) for d in plan.dst),
        )
        return self._fetch_plan

    # -- execute ---------------------------------------------------------------

    @property
    def widths(self) -> tuple[int, ...]:
        """Facet width per axis (0 for axes that carry no facet)."""
        return tuple(
            self.specs[a].width if a in self.specs else 0
            for a in range(self.space.ndim)
        )

    def _interior_slices(self, w: tuple[int, ...]) -> tuple[slice, ...]:
        """Index of the tile interior within a (w + t)-shaped halo buffer
        (every field)."""
        return self.program.with_fields(
            tuple(slice(w[a], None) for a in range(self.space.ndim)), slice(None))

    def execute_tile(self, H: jnp.ndarray) -> jnp.ndarray:
        """Run the plane recurrence over the halo buffer; returns the filled
        buffer (interior planes computed in place)."""
        w = self.widths
        t = self.tiling.sizes
        depth = w[0]
        spatial = self._interior_slices(w)[1:]
        for s in range(t[0]):
            prev = [H[w[0] + s - m] for m in range(depth, 0, -1)]
            plane = self.program.plane_update(prev, w)
            H = H.at[(w[0] + s, *spatial)].set(plane)
        return H

    def execute_wave(self, halos) -> tuple[jnp.ndarray, ...]:
        """Run :meth:`execute_tile` over a wave's halo buffers as one
        device program: stack them, ``jax.vmap`` the recurrence, and
        return each tile's filled buffer.

        One jitted program a pipeline, built on the first call; it
        compiles once per distinct wave size, and the halos are donated
        to it (each filled buffer may take its halo's memory).  The
        arithmetic is :meth:`execute_tile`'s, but XLA may fuse a multiply
        and an add into one rounding where the eager recurrence rounds
        twice."""
        if self._wave_program is None:
            def run(hs):
                return tuple(jax.vmap(self.execute_tile)(jnp.stack(hs)))

            self._wave_program = jax.jit(run, donate_argnums=0)
        return self._wave_program(tuple(halos))

    # -- copy-out ---------------------------------------------------------------

    def copy_out(
        self, facets: dict[int, jnp.ndarray], tile: tuple[int, ...], H: jnp.ndarray
    ) -> dict[int, jnp.ndarray]:
        """Write the tile's facet blocks from the interior of its filled
        halo buffer ``H``: per facet array, the last ``w`` planes of the
        interior along the facet's axis, laid out as one block.

        Facets on one device take the compiled commit: the pipeline's
        :class:`CommitPlan`, built on the first such call, holds every
        tile's block indices and permutations on the device, and one
        jitted program per pipeline stores the tile's blocks with the
        facet arrays donated (updated in place; the arrays passed in are
        deleted, so a caller keeps only the returned dict).  Facets over
        several devices take the eager :meth:`_store_block`, block by
        block.  Where the facets sit is the only choice.
        """
        rec = self.recorder
        compiled = _on_one_device(facets)
        with obs.phase(rec, "copy_out", "commit", fields=self.fields,
                       after=lambda: rec.record_write(self, tile)):
            if rec is not None:
                rec.counters.add("commit_compiled" if compiled else "commit_eager", 1)
            out = dict(facets)
            if compiled:
                plan = self._commit_plan or self._build_commit_plan()
                out.update(zip(plan.keys, plan.commit(
                    tuple(facets[k] for k in plan.keys), plan.index,
                    plan.perm, plan.rows[tile], H)))
                return out
            for k, spec in self.specs.items():
                out[k] = self._store_block(out[k], spec, tile,
                                           self._commit_slab(H, k))
        return out

    def _commit_slab(self, H: jnp.ndarray, k: int) -> jnp.ndarray:
        """The slab facet ``k`` stores of a filled halo buffer: the
        interior's last ``w_k`` planes along axis ``k`` (every field)."""
        sl = list(self._interior_slices(self.widths))
        # the interior starts at w_k, so its last w_k planes at t_k
        sl[self._array_axis(k)] = slice(self.tiling.sizes[k], None)
        return H[tuple(sl)]

    # -- compiled commit ---------------------------------------------------------

    def commit_tables(self) -> "CommitPlan":
        """Resolve every block store once, on the host: the
        :class:`CommitPlan` with numpy tables and each tile's row number
        (no programs).  Rows follow the tiles in lexicographic order;
        facet_0's live-in rows come after them."""
        tiles = list(itertools.product(*(range(n) for n in self.num_tiles)))
        live = [(-1, *q) for q, _ in self._live_in_blocks()]
        index, perm = [], []
        for k, spec in self.specs.items():
            blocks = tiles + live if k == 0 else tiles
            index.append(np.array([self._block_index(spec, b) for b in blocks],
                                  np.int64).reshape(len(blocks), -1))
            perm.append(np.array([self._block_perm(spec, b) for b in blocks],
                                 np.int64))
        return CommitPlan(
            rows={tile: r for r, tile in enumerate(tiles)},
            keys=tuple(self.specs), index=tuple(index), perm=tuple(perm))

    def _build_commit_plan(self) -> "CommitPlan":
        """Build this pipeline's commit plan: upload its tables and row
        numbers, each once, through :func:`device_index`, and jit its two
        programs, the facet arrays donated."""
        plan = self.commit_tables()
        rows = list(device_index(np.arange(len(plan.rows))))

        def commit(facets, index, perm, row, H):
            return tuple(
                self._put_block(arr, self.specs[k], tuple(ix[row]), pm[row],
                                self._commit_slab(H, k))
                for arr, k, ix, pm in zip(facets, plan.keys, index, perm))

        def load(f0, index, perm, inputs):
            for r, (_, sl) in enumerate(self._live_in_blocks(), len(plan.rows)):
                f0 = self._put_block(f0, self.specs[0], tuple(index[r]),
                                     perm[r], inputs[sl])
            return f0

        self._commit_plan = dataclasses.replace(
            plan,
            rows={tile: rows[r] for tile, r in plan.rows.items()},
            index=tuple(device_index(ix) for ix in plan.index),
            perm=tuple(device_index(pm) for pm in plan.perm),
            commit=jax.jit(commit, donate_argnums=0),
            load=jax.jit(load, donate_argnums=0),
        )
        return self._commit_plan

    # -- full sweep ----------------------------------------------------------------

    def _sweep(self, inputs: jnp.ndarray, dtype=jnp.float32) -> dict[int, jnp.ndarray]:
        """Run the whole tiled computation through facet storage (the
        ``backend="sweep"`` executor's entry point)."""
        rec = self.recorder
        facets = self._loaded_facets(inputs, dtype)
        if rec is not None:
            rec.counters.add("waves", len(self.wavefronts()))
        for tile in itertools.product(*(range(n) for n in self.num_tiles)):
            H = self.copy_in(facets, tile)
            with obs.phase(rec, "execute_tile", "compute",
                           tile=list(tile), wave=int(sum(tile)), fields=self.fields):
                H = self.execute_tile(H)
            if rec is not None:
                rec.counters.add("execute_eager", 1)
            facets = self.copy_out(facets, tile, H)
        return facets

    def _loaded_facets(self, inputs: jnp.ndarray, dtype) -> dict[int, jnp.ndarray]:
        """Fresh facet arrays with the live-in planes loaded: the
        ``load_inputs`` phase every executor opens its sweep with."""
        rec = self.recorder
        if rec is not None:
            rec.counters.add("facet_fields", self.fields)
        with obs.phase(rec, "load_inputs", "commit"):
            return self.load_inputs(self.init_facets(dtype), inputs.astype(dtype))

    # -- wavefront-parallel sweep ------------------------------------------------

    def wavefronts(self) -> list[list[tuple[int, ...]]]:
        """Tiles grouped by wavefront (sum of tile coordinates).

        All backward-neighbour dependencies strictly decrease the coordinate
        sum, so tiles within one wavefront are independent — the tile-level
        parallelism the paper's task pipeline generalises to on a machine
        with many cores/ports."""
        waves: dict[int, list[tuple[int, ...]]] = {}
        for tile in itertools.product(*(range(n) for n in self.num_tiles)):
            waves.setdefault(sum(tile), []).append(tile)
        return [waves[s] for s in sorted(waves)]

    def _sweep_wavefront(self, inputs: jnp.ndarray, dtype=jnp.float32,
                         use_kernel: bool = False,
                         interpret: bool | None = None) -> dict[int, jnp.ndarray]:
        """Wavefront-parallel sweep: each wave's tiles execute as one batch,
        through the Pallas tile executor when ``use_kernel``, else as one
        compiled plane recurrence (:meth:`execute_wave`) — the
        ``backend="wavefront"``/``"pallas"`` executors' entry point."""
        rec = self.recorder
        facets = self._loaded_facets(inputs, dtype)
        interior = self._interior_slices(self.widths)
        waves = self.wavefronts()
        if rec is not None:
            rec.counters.add("waves", len(waves))
        for wave in waves:
            gathered = [self.copy_in(facets, t) for t in wave]
            # the batch and the interiors' write-back exist only for the
            # kernel or recurrence, so they are timed as part of it
            with obs.phase(rec, "execute_wave", "compute",
                           wave=int(sum(wave[0])), n_tiles=len(wave),
                           tiles=[list(t) for t in wave], fields=self.fields):
                if use_kernel:
                    from repro.kernels.stencil import execute_tiles

                    halos = jnp.stack(gathered)
                    # free the per-tile halos now, or they stay on the
                    # device beside the batch into the next wave's fetch
                    del gathered
                    interiors = execute_tiles(self.program.name, halos,
                                              self.tiling.sizes,
                                              interpret=interpret)
                    outs = [halos[i].at[interior].set(interiors[i])
                            for i in range(len(wave))]
                else:
                    # the halos are donated: the program takes their memory
                    outs = self.execute_wave(gathered)
                    if rec is not None:
                        rec.counters.add("execute_compiled", len(wave))
            for tile, H in zip(wave, outs):
                facets = self.copy_out(facets, tile, H)
        return facets

    # -- dataflow (overlapped) sweep ----------------------------------------

    def _sweep_dataflow(self, inputs: jnp.ndarray, dtype=jnp.float32,
                        use_kernel: bool = False,
                        interpret: bool | None = None) -> dict[int, jnp.ndarray]:
        """Software-pipelined wavefront sweep: fetch, compute and commit of
        consecutive tiles overlap (the host realisation of Fig. 13 DATAFLOW).

        Same plane update and same facet-commit order as
        ``_sweep_wavefront`` — only the *interleaving* changes: while tile
        ``j``'s execute is in flight (jax dispatches it asynchronously),
        tile ``j+1``'s halo is gathered and tile ``j-1``'s result is
        committed.  This is legal because every halo point a wave-``s``
        tile reads was committed by a strictly earlier wave (backward deps
        decrease the coordinate sum — see :meth:`wavefronts`), so a fetch
        never races a same-wave commit.

        The host path hands each gathered halo to a donated jitted staging
        buffer (``jax.jit(..., donate_argnums=0)``): the previous tile's
        halo memory is reused for the next tile — a ping-pong staging pair
        instead of a fresh allocation per tile — while the plane recurrence
        itself runs through the very same eager ``execute_tile`` the sweep
        executor uses, keeping the host path bit-exact.  The kernel path
        runs each tile through the Pallas executor (``execute_tiles``),
        whose grid pipeline double-buffers HBM<->VMEM copies against
        compute in hardware.
        """
        rec = self.recorder
        facets = self._loaded_facets(inputs, dtype)
        interior = self._interior_slices(self.widths)
        if use_kernel:
            from repro.kernels.stencil import execute_tiles

            def _dispatch(H):
                out = execute_tiles(self.program.name, H[None],
                                    self.tiling.sizes, interpret=interpret)
                return H.at[interior].set(out[0])
        else:
            stage = jax.jit(lambda h: h, donate_argnums=0)

            def _dispatch(H):
                with warnings.catch_warnings():
                    # backends without donation support (CPU jax) warn and
                    # fall back to a copy; the staging is then a no-op,
                    # not an error
                    warnings.filterwarnings("ignore", message=r".*[Dd]onat")
                    H = stage(H)
                if rec is not None:
                    rec.counters.add("execute_eager", 1)
                return self.execute_tile(H)

        waves = self.wavefronts()
        if rec is not None:
            rec.counters.add("waves", len(waves))
        for wave in waves:
            nxt = self.copy_in(facets, wave[0])
            prev_tile: tuple[int, ...] | None = None
            prev_out = None
            prev_tok: int | None = None
            for j, tile in enumerate(wave):
                # the compute span brackets the whole in-flight window:
                # dispatch here, closed when this tile's commit begins —
                # so the next tile's prefetch (and the previous tile's
                # commit) land *inside* it as concurrent lanes
                tok = rec.begin("execute_tile", track=rec.track("compute"),
                                tile=list(tile), wave=int(sum(tile)),
                                port=rec.port) if rec is not None else None
                H = _dispatch(nxt)  # async: compute in flight from here on
                if j + 1 < len(wave):
                    nxt = self.copy_in(facets, wave[j + 1])  # prefetch
                if prev_tile is not None:
                    if prev_tok is not None:
                        rec.end(prev_tok)
                    facets = self.copy_out(facets, prev_tile, prev_out)
                prev_tile, prev_out, prev_tok = tile, H, tok
            if prev_tok is not None:
                rec.end(prev_tok)
            facets = self.copy_out(facets, prev_tile, prev_out)
        return facets

    # -- multi-port sharded sweep -------------------------------------------

    def _sweep_wavefront_sharded(
        self,
        inputs: jnp.ndarray,
        dtype=jnp.float32,
        *,
        n_ports: int = 2,
        mesh=None,
        axis: str = "port",
        assignment=None,
        use_kernel: bool = False,
        interpret: bool | None = None,
    ) -> dict[int, jnp.ndarray]:
        """Multi-port wavefront sweep: facet arrays sharded over a mesh axis
        per the port repartition, anti-diagonal tile waves executed in
        parallel via ``shard_map`` (paper §VII made an execution path) —
        the ``backend="sharded"`` executor's entry point.

        * the facet arrays are placed on their assigned port's device
          (``repro.distributed.sharding.shard_facets``; the facet array is the
          unit of contiguity, so facet-granular repartition == whole-array
          placement — ``assignment`` defaults to this pipeline's compile-time
          ``port_assignment`` (the port_repartition pass), then the autotuned
          decision's split, then the LPT split of ``multiport.assign_ports``);
        * every wavefront's tiles are independent (backward deps strictly
          decrease the coordinate sum), so each wave is batched, padded to a
          multiple of the mesh axis, and executed concurrently — one shard of
          tiles per port — through ``execute_tiles_sharded`` (Pallas kernel
          per shard) when ``use_kernel``, else an inline ``shard_map`` of the
          plane recurrence.

        Bit-exact against the single-port ``_sweep``: device placement and
        shard_map batching change *where* tiles run, never the plane
        arithmetic or the order facet blocks are committed.
        """
        from jax.sharding import NamedSharding

        from repro.core.cfa.multiport import assign_ports
        from repro.distributed.sharding import (
            P, port_mesh, shard_facets)

        if assignment is None:
            pa = self.port_assignment
            if pa is not None and getattr(pa, "n_ports", None) == n_ports:
                assignment = pa
        if assignment is None:
            decision = self.decision
            if decision is not None and getattr(decision, "n_ports", 1) == n_ports:
                # only reuse the decision's facet->port split when this
                # pipeline instantiates the candidate it was computed for
                # (a pipeline built by hand may tile differently)
                try:
                    best = decision.best_cfa()
                except LookupError:
                    best = None
                if best is not None and tuple(best.candidate.tile) == self.tiling.sizes:
                    assignment = decision.port_assignment  # may still be None
        if assignment is None:
            assignment = assign_ports(self.space, self.program.deps,
                                      self.tiling, n_ports)
        mesh = mesh if mesh is not None else port_mesh(n_ports, axis)
        n_shards = int(mesh.shape[axis])

        facets = self._loaded_facets(inputs, dtype)
        facets = shard_facets(facets, assignment.facet_to_port, mesh, axis)

        interior = self._interior_slices(self.widths)

        def _exec_batch(halos: jnp.ndarray) -> jnp.ndarray:
            # one shard of the wave per port-device; each tile runs the very
            # same execute_tile recurrence as the single-port sweep
            return jax.shard_map(
                jax.vmap(self.execute_tile), mesh=mesh,
                in_specs=P(axis), out_specs=P(axis),
            )(halos)

        batch_sharding = NamedSharding(mesh, P(axis))
        rec = self.recorder
        waves = self.wavefronts()
        if rec is not None:
            rec.counters.add("waves", len(waves))
        for wave in waves:
            # pad the wave to a multiple of the mesh axis by repeating tiles
            # (a wave can be smaller than the axis — e.g. the first wave is
            # always one tile — so slicing the batch itself cannot under-pad)
            target = -(-len(wave) // n_shards) * n_shards
            gathered = []
            for i, t in enumerate(wave):
                if rec is not None:
                    # tile i runs on shard i of the padded batch — group its
                    # spans under that port's lanes
                    rec.port = i * n_shards // target
                gathered.append(self.copy_in(facets, t))
            halos = jnp.stack(gathered)
            if rec is not None:
                rec.port = 0
            if target != len(wave):
                reps = -(-target // len(wave))
                halos = jnp.concatenate([halos] * reps, axis=0)[:target]
            # commit the batch to the port mesh: one shard of tiles per port
            halos = jax.device_put(halos, batch_sharding)
            with obs.phase(rec, "execute_wave", "compute",
                           wave=int(sum(wave[0])), n_tiles=len(wave),
                           n_ports=n_shards):
                if use_kernel:
                    from repro.kernels.stencil import execute_tiles_sharded

                    interiors = execute_tiles_sharded(
                        self.program.name, halos, self.tiling.sizes, mesh,
                        axis=axis, interpret=interpret)
                    outs = halos.at[(slice(None), *interior)].set(interiors)
                else:
                    outs = _exec_batch(halos)
                # pull the executed planes back uncommitted so copy_out's
                # facet updates stay resident on each facet's own port device
                outs = np.asarray(jax.device_get(outs))
            for i, tile in enumerate(wave):
                if rec is not None:
                    rec.port = i * n_shards // target
                facets = self.copy_out(facets, tile, jnp.asarray(outs[i]))
            if rec is not None:
                rec.port = 0
        return facets

    # -- oracle ----------------------------------------------------------------

    def reference_volume(self, inputs: jnp.ndarray) -> jnp.ndarray:
        """Untiled plane-by-plane sweep over the full space (the oracle)."""
        w = self.widths
        N = self.space.sizes
        depth = w[0]
        pad = self.program.with_fields(
            [(w[a], 0) for a in range(self.space.ndim)], (0, 0))[1:]
        hist = [jnp.asarray(inputs[m]) for m in range(depth)]  # planes -w0..-1
        planes = []
        for _ in range(N[0]):
            padded = [jnp.pad(h, pad) for h in hist]
            new = self.program.plane_update(padded, w)
            planes.append(new)
            hist = hist[1:] + [new] if depth > 1 else [new]
        return jnp.stack(planes)
