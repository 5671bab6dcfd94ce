from .facet_fetch import fetch_interior_halos
from .ref import fetch_interior_halos_ref

__all__ = [
    "fetch_interior_halos",
    "fetch_interior_halos_ref",
    "fetch_interior_halos_sharded",
]


def fetch_interior_halos_sharded(program_name, facets, space, tile,
                                 assignment, mesh=None, *, axis="port",
                                 interpret=None, storage="redundant"):
    """Block-wise halo fetch with facet arrays resident on their ports.

    The multi-port analogue of ``fetch_interior_halos``: the facet arrays are
    first placed on their assigned port's device
    (``repro.distributed.sharding.shard_facets``), then each is pulled into
    the fetch engine's device with one explicit transfer per facet — the
    read traffic sources from the port that owns each facet, exactly as the
    ``assignment`` (a ``multiport.PortAssignment``) prescribes.  (The jit'd
    kernel itself runs on one device: its BlockSpec DMAs model the per-port
    channel reads, as on real hardware where every HBM channel feeds the
    same compute die.)  Returns the same
    (n0-1, n1-1, n2-1, w0+t0, w1+t1, w2+t2) halo volume.
    """
    import jax

    from repro.distributed.sharding import port_mesh, shard_facets

    if mesh is None:
        mesh = port_mesh(assignment.n_ports, axis)
    facets = shard_facets(facets, assignment.facet_to_port, mesh, axis)
    # one transfer per facet, sourced from its owning port's device (skipped
    # for facets already resident there, e.g. a single-device mesh)
    dev0 = list(mesh.devices.reshape(-1))[0]
    facets = {
        k: v if getattr(v, "devices", None) is not None and v.devices() == {dev0}
        else jax.device_put(v, dev0)
        for k, v in facets.items()
    }
    return fetch_interior_halos(program_name, facets, tuple(space),
                                tuple(tile), interpret=interpret,
                                storage=storage)
