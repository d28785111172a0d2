"""Faults planted in the timed path, to show that the comparison which
decides ``correct`` catches them. The benchmark's own runs never plant
one; ``bench/readings.py`` reads them at a cell's own size and the tests
at a small one.

  * ``altered``: every registered pose moved 0.3 m in x where the slot
    engine produces it;
  * ``half``: the upper half of the batch left out, its poses left at
    their warm start, as if never registered;
  * ``unchanged``: the map fuse returns the fleet's maps as it found
    them (a step that leaves its state unchanged);
  * ``map_shifted``: the fused maps' centroids moved 5 cm in x where the
    fuse produces them.

One chip, so there is no exchange between chips to leave out.
"""
from __future__ import annotations

FAULTS = ("altered", "half", "unchanged", "map_shifted")


def _warm_start(T, initial):
    import jax.numpy as jnp
    if initial is None:
        return jnp.broadcast_to(jnp.eye(4, dtype=T.dtype), T.shape)
    return jnp.asarray(initial, T.dtype)


def _broken_registration(orig, fault):
    import jax.numpy as jnp

    def broken(self, sources, targets, params=None, *, src_valid=None,
               dst_valid=None, initial_transforms=None):
        res = orig(self, sources, targets, params, src_valid=src_valid,
                   dst_valid=dst_valid,
                   initial_transforms=initial_transforms)
        T = jnp.asarray(res.T)
        if fault == "altered":
            return res._replace(T=T.at[:, 0, 3].add(0.3))
        half = T.shape[0] // 2
        T0 = _warm_start(T, initial_transforms)
        return res._replace(T=T.at[half:].set(T0[half:]))

    return broken


def _broken_fuse(orig, fault):
    import jax.numpy as jnp

    def broken(state_b, scan_b, valid_b, pose_b, accept_b, params):
        if fault == "unchanged":
            _, occ, drop = orig(tuple(jnp.copy(x) for x in state_b),
                                scan_b, valid_b, pose_b, accept_b, params)
            return state_b, occ, drop
        (pts, valid, origin), occ, drop = orig(state_b, scan_b, valid_b,
                                               pose_b, accept_b, params)
        moved = jnp.where(valid[..., None], pts.at[..., 0].add(0.05), pts)
        return (moved, valid, origin), occ, drop

    return broken


def plant(fault: str, setattr_=setattr):
    """Plant ``fault`` in the program; returns a function that removes
    it. ``setattr_`` may be a test's ``monkeypatch.setattr``."""
    from repro.core import engine as engine_mod
    from repro.serve import registration_service as service_mod
    if fault in ("altered", "half"):
        owner, name = engine_mod.SlotEngine, "register_batch"
        new = _broken_registration(getattr(owner, name), fault)
    elif fault in ("unchanged", "map_shifted"):
        owner, name = service_mod, "fuse_lanes"
        new = _broken_fuse(getattr(owner, name), fault)
    else:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    orig = getattr(owner, name)
    setattr_(owner, name, new)
    return lambda: setattr(owner, name, orig)
