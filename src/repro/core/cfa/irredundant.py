"""Irredundant facet storage: every canonical value stored exactly once.

The paper's facet layout buys burst contiguity by *duplicating* halo data:
a point in the tail slab of several axes lies in several facets' projection
domains and is stored — and written — once per facet (``TransferPlan``
measures the tax as ``redundancy``).  The authors' follow-up (Ferry et al.,
2024, *An Irredundant and Compressed Data Layout...*) removes the duplicates
by giving every point exactly one **owner** facet; this module is that
storage discipline as a first-class subsystem:

* :func:`owner_of` — the deterministic ownership rule: a point in several
  facet domains is owned by the **lowest** facet axis (the time facet wins
  corners, matching the paper's host preference for the thinnest/first axis).
  Ownership depends only on intra-tile coordinates, so it is a static,
  tile-independent mask over each facet block.
* :class:`StorageMap` / :func:`build_storage_map` — the per-facet owned
  masks plus the footprint accounting: ``stored_elems`` (each value once),
  ``redundant_elems`` (the paper's layout), ``redundancy`` (stored /
  distinct — 1.0 by construction, pinned by tests), ``savings``.
* :func:`dedup_facets` / :func:`rehydrate_facets` — drop non-owned slots
  (they read as zeros) / refill them from their owner facets, so an
  irredundant execution payload compares bit-for-bit against the redundant
  one.
* :class:`IrredundantPipeline` — a ``CFAPipeline`` whose ``copy_out``
  commits only owned slots and whose ``copy_in`` resolves every halo read
  to the owner facet's storage (the owner-facet indirection; the compiled
  fetch's ``FetchPlan`` bakes the same owner reads into its tables).
* :class:`CompressedPipeline` — additionally passes every committed block
  through a fixed-ratio :class:`~repro.core.cfa.compress.BlockCodec`
  round-trip, so results reflect exactly what compressed storage preserved
  (bit-identical under an exact codec; the transfer-time effect is modeled
  by ``BurstModel`` via ``TransferPlan.codec_bits``).

The burst-accounting counterpart (owner-resolved reads, owned-run writes,
``footprint``/``stored_elems`` on the plan) lives in
``repro.core.cfa.plans.cfa_plan(storage="irredundant")``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import ClassVar, Mapping

import numpy as np
import jax.numpy as jnp

from .compress import BlockCodec, get_codec
from .facets import FacetSpec, row_major_strides
from .transform import CFAPipeline, device_index

__all__ = [
    "STORAGE_MODES",
    "owner_of",
    "StorageMap",
    "build_storage_map",
    "dedup_facets",
    "rehydrate_facets",
    "IrredundantPipeline",
    "CompressedPipeline",
]

#: The three facet storage disciplines ``cfa.compile`` exposes: the paper's
#: duplicated layout, the deduplicated one, and deduplicated + fixed-ratio
#: block compression (Ferry 2024).
STORAGE_MODES = ("redundant", "irredundant", "compressed")


def owner_of(specs: Mapping[int, FacetSpec], pts: np.ndarray) -> np.ndarray:
    """Owner facet axis per point: the lowest axis whose projection domain
    contains the point; ``-1`` for points in no facet domain."""
    pts = np.atleast_2d(np.asarray(pts, dtype=np.int64))
    owner = np.full(len(pts), -1, dtype=np.int64)
    for k in sorted(specs):  # ascending axis == ownership priority
        m = (owner < 0) & specs[k].domain_mask(pts)
        owner[m] = k
    return owner


@dataclasses.dataclass(frozen=True)
class StorageMap:
    """The irredundant storage discipline for one facet family.

    ``owned[k]`` is a boolean mask over facet ``k``'s *block* (inner dims,
    in ``inner_axes`` order): True where the slot's canonical point is owned
    by facet ``k``.  Ownership never depends on the axis-``k`` (modulo)
    coordinate, so the masks are exact for tile-dependent modulo labelling
    too, and identical for every tile block.
    """

    specs: dict[int, FacetSpec]
    owned: dict[int, np.ndarray]

    @property
    def owned_per_block(self) -> dict[int, int]:
        """Owned slots in one tile's block, per facet (every field's)."""
        return {k: int(m.sum()) * self.specs[k].fields
                for k, m in self.owned.items()}

    def stores(self, k: int, pts: np.ndarray) -> np.ndarray:
        """Boolean per point: does facet ``k`` *store* it — i.e. the point
        lies in facet ``k``'s projection domain *and* lands on an owned
        slot?  Summed over facets this counts a point's storage slots; the
        static verifier (``analysis.check_facet_family``) proves the count
        is exactly one over the whole family."""
        spec = self.specs[k]
        pts = np.atleast_2d(np.asarray(pts, dtype=np.int64))
        out = np.zeros(len(pts), dtype=bool)
        dom = spec.domain_mask(pts)
        if dom.any():
            inner = spec.coords(pts[dom])[:, len(spec.outer_axes):]
            out[np.flatnonzero(dom)] = self.owned[k][tuple(inner.T)]
        return out

    @property
    def stored_elems(self) -> int:
        """Total slots the irredundant layout stores (each value once)."""
        return sum(
            self.owned_per_block[k] * (s.size // s.block_elems)
            for k, s in self.specs.items()
        )

    @property
    def redundant_elems(self) -> int:
        """Total slots the paper's duplicated layout stores."""
        return sum(s.size for s in self.specs.values())

    @property
    def redundancy(self) -> float:
        """Stored slots per distinct value — 1.0: single assignment.

        The ownership rule partitions every tile's facet union, so this is
        1.0 *by construction*; the property tests verify the partition on
        random spaces rather than trusting the closed form.
        """
        return 1.0 if self.stored_elems else 0.0

    @property
    def savings(self) -> float:
        """Fraction of the redundant layout's slots the dedup removes."""
        red = self.redundant_elems
        return 0.0 if not red else 1.0 - self.stored_elems / red


def build_storage_map(specs: Mapping[int, FacetSpec]) -> StorageMap:
    """Derive the owned masks for a facet family.

    A slot of facet ``k``'s block with intra-tile coordinate ``r`` is owned
    iff no lower-axis facet ``j < k`` also covers it, i.e. iff
    ``r_j < t_j - w_j`` for every facet axis ``j < k`` — the complement of
    facet ``j``'s tail slab.  (Facet ``k`` covers its own block by
    definition, and the axis-``k`` inner coordinate is the modulo label,
    which ownership never consults.)
    """
    owned: dict[int, np.ndarray] = {}
    for k, spec in specs.items():
        mask = np.ones(
            tuple(spec.inner_size(a) for a in spec.inner_axes), dtype=bool
        )
        for pos, a in enumerate(spec.inner_axes):
            if a < k and a in specs:
                t_a, w_a = spec.tile_sizes[a], specs[a].width
                sl = [slice(None)] * mask.ndim
                sl[pos] = slice(t_a - w_a, t_a)
                mask[tuple(sl)] = False
        owned[k] = mask
    return StorageMap(specs=dict(specs), owned=owned)


def dedup_facets(
    facets: dict[int, jnp.ndarray], smap: StorageMap
) -> dict[int, jnp.ndarray]:
    """Zero the non-owned slots (what irredundant storage never writes);
    a mask covers every field alike."""
    out = {}
    for k, arr in facets.items():
        mask = smap.owned[k]
        if mask.all():
            out[k] = arr
        else:  # masks cover the inner dims; outer (tile) dims broadcast
            out[k] = jnp.where(jnp.asarray(mask), arr, jnp.zeros((), arr.dtype))
    return out


def _virtual_shift(spec: FacetSpec, arr: jnp.ndarray) -> int:
    """Flat-offset shift when ``arr`` carries extra leading block rows
    beyond ``spec.shape`` (facet_0's virtual live-in row)."""
    extra = arr.shape[0] - spec.shape[0]
    return extra * int(np.prod(spec.shape[1:], dtype=np.int64))


def rehydrate_facets(
    facets: dict[int, jnp.ndarray], smap: StorageMap
) -> dict[int, jnp.ndarray]:
    """Refill every non-owned slot from its owner facet's storage.

    The inverse of :func:`dedup_facets` given owner values: applied to an
    irredundant execution payload it reconstructs the redundant payload
    bit-for-bit (duplicated slots duplicate the owner's value by
    construction — both were committed from the same tile interior).
    Facet_0's virtual live-in row passes through untouched: facet_0 is
    fully owned (lowest axis), and dead slots of other facets decode to
    in-space points, whose owner storage is a real (shifted) facet_0 row.
    """
    specs = smap.specs
    out = dict(facets)
    for k, spec in specs.items():
        mask = smap.owned[k]
        if mask.all():
            continue
        arr = facets[k]
        n_outer = len(spec.outer_axes)
        # decode every dead slot of the full array to its canonical point
        full_mask = np.broadcast_to(
            mask, tuple(arr.shape[:n_outer]) + mask.shape
        )
        dead = np.argwhere(~full_mask)  # (n, outer+inner) multi-indices
        t = np.asarray(spec.tile_sizes, dtype=np.int64)
        q = np.zeros((len(dead), spec.ndim), dtype=np.int64)
        for col, a in enumerate(spec.outer_axes):
            q[:, a] = dead[:, col]
        x = np.zeros((len(dead), spec.ndim), dtype=np.int64)
        for col, a in enumerate(spec.inner_axes):
            c = dead[:, n_outer + col]
            if a == spec.axis:  # modulo label -> slab position (per tile)
                w = spec.width
                base = q[:, a] * t[a] + t[a] - w
                x[:, a] = base + (c - base) % w
            else:
                x[:, a] = q[:, a] * t[a] + c
        own = owner_of(specs, x)
        if (own < 0).any() or (own >= k).any():
            raise AssertionError(
                "dead slot without a lower-axis owner — storage-map bug"
            )
        # every field of a dead slot, field after field
        F = spec.fields
        vals = jnp.zeros(F * len(dead), arr.dtype)
        for j in np.unique(own):
            sel = np.flatnonzero(own == j)
            offs = specs[j].offsets(x[sel])
            vals = vals.at[np.concatenate([sel + f * len(dead) for f in range(F)])].set(
                facets[j].reshape(-1)[device_index(offs + _virtual_shift(specs[j], facets[j]))]
            )
        point_shape = (arr.shape[0], *spec.point_shape[1:])
        flat_idx = spec.spread_fields(dead @ row_major_strides(point_shape))
        out[k] = arr.reshape(-1).at[device_index(flat_idx)].set(vals).reshape(arr.shape)
    return out


# --------------------------------------------------------------------------
# Execution pipelines
# --------------------------------------------------------------------------


@dataclasses.dataclass
class IrredundantPipeline(CFAPipeline):
    """``CFAPipeline`` under the irredundant storage discipline.

    Same facet shapes, same schedule, two overrides:

    * ``copy_out`` (via ``_store_block``) commits only owned slots — a
      value is written exactly once, to its owner facet;
    * ``copy_in`` (via ``_halo_hosts``) reads every halo point from its
      owner facet, whether or not that facet's axis is crossed — the
      owner-facet indirection (non-owned slots hold nothing).

    The payload therefore has zeros in every non-owned slot; pass it
    through :func:`rehydrate_facets` to compare against a redundant run.
    """

    storage: ClassVar[str] = "irredundant"
    storage_map: StorageMap = dataclasses.field(init=False, repr=False, compare=False)

    def __post_init__(self) -> None:
        super().__post_init__()
        self.storage_map = build_storage_map(self.specs)

    def _halo_hosts(self, pts, lo, taken):
        """Owner-priority halo sourcing: ascending facet axis, domain
        membership only (the crossing direction is irrelevant to where a
        value is *stored*)."""
        maps = {}
        for k, spec in self.specs.items():
            mask = ~taken & spec.domain_mask(pts)
            if mask.any():
                maps[k] = pts[mask]
                taken |= mask
        return maps

    def _commit_block(self, arr, idx, block, spec):
        mask = self.storage_map.owned[spec.axis]
        if mask.all():
            return super()._commit_block(arr, idx, block, spec)
        # owned slots get the new value; non-owned slots stay untouched
        # (the mask covers the inner dims, so every field alike)
        return arr.at[idx].set(jnp.where(jnp.asarray(mask), block, arr[idx]))


@dataclasses.dataclass
class CompressedPipeline(IrredundantPipeline):
    """Irredundant storage + fixed-ratio block compression (Ferry 2024).

    Every committed block is passed through the codec's encode/decode
    round-trip before storage, so the facets hold exactly what compressed
    memory would return — bit-identical to the irredundant pipeline when
    the codec is exact on the data (e.g. the ``raw`` codec, or bit-truncated
    inputs under ``deltapack16``), measurably quantised otherwise.  The
    bytes-per-burst effect is modeled by ``BurstModel`` via
    ``TransferPlan.codec_bits``, not re-simulated here.
    """

    storage: ClassVar[str] = "compressed"
    codec: BlockCodec | str | None = None

    def __post_init__(self) -> None:
        super().__post_init__()
        self.codec = get_codec(self.codec)

    def _commit_block(self, arr, idx, block, spec):
        # storage holds the block layout, so the codec sees it as written
        return super()._commit_block(arr, idx, self.codec.roundtrip(block),
                                     spec)
