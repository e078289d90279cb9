"""``mesh_fanout_push`` — the cohort δ fan-out dispatch (ISSUE 16).

One jitted shard_map computes a whole dispatch of per-cohort
join-irreducible δ payloads against the serve superblock: the lane
axis shards over the REPLICA mesh axis (cohorts are independent — zero
cross-cohort collectives), each device gathers its touched tenant
rows, vmap-decomposes them against the cohort base rows
(ops/fanout_kernels.cohort_deltas), and runs the WHOLE local batch
through ONE fused wire-pack pass (cohort_wire_encode — the PR 14
kernel generalized from P ring links to B·E client lanes).

Index convention matches ``mesh_serve_apply``: ``idx[B] int32``
carries LOCAL row indices — lane block ``[r·B/P, (r+1)·B/P)`` belongs
to mesh rank ``r`` and its values index that rank's local tenant rows
``[0, T/P)``; ``-1`` lanes are empty (their wire lanes zero and their
byte price drops). The host-side subscription plane
(crdt_tpu/fanout/plane.py) owns this layout via the superblock's
tenant→lane indirection. ``bases[B, ...]`` stacks each cohort's acked
base row (the plane's promote-on-ack copy — delta_opt/ackwin.py
semantics), sharded alongside the lanes; ``weights[B]`` carries cohort
sizes so the byte telemetry prices every subscriber delivery, not just
every cohort.

The dispatch only READS the superblock — nothing donates
(``n_donated=0``; the aliasing gate sees a pure read). ``telemetry=``
follows the house rules: off traces the byte-identical flag-free
program; on returns a Telemetry sidecar — ``cohorts_per_dispatch`` /
``delta_push_bytes`` psum'd over the replica axis, the per-cohort
prices observed into the ``hist_push_bytes`` in-kernel histogram in
one vectorized scatter (obs/hist.observe_vec). The host-owned
``subscribers_live`` gauge and ``resync_fallbacks`` counter are filled
by the plane (the ``stream_*``/``wal_*`` fill discipline).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from .. import telemetry as tele
from ..obs import hist as obs_hist
from ..ops import superblock as sb_ops
from ..ops.fanout_kernels import (
    cohort_deltas,
    cohort_push_bytes,
    cohort_wire_encode,
)
from ..utils.metrics import metrics
from .anti_entropy import _cached
from .mesh import REPLICA_AXIS


def _validate(state, bases, idx, p: int) -> None:
    t = jax.tree.leaves(state)[0].shape[0]
    b = jax.tree.leaves(bases)[0].shape[0]
    if t % p:
        raise ValueError(
            f"{t} tenant rows do not divide the {p}-way replica axis"
        )
    if b % p or idx.shape[0] != b:
        raise ValueError(
            f"base lanes ({b}) and idx ({idx.shape[0]}) must match and "
            f"divide the {p}-way replica axis"
        )


def _local_push(kind: str, state, bases, idx):
    """The per-device core (also traced under ``jax.eval_shape``, once
    per compiled program, to derive the wire's out_specs): gather →
    vmapped decompose vs the cohort bases → one fused wire-pack over
    the local batch → the per-cohort byte price. Empty lanes
    (``idx < 0``) zero out of the lane mask, the wire, and the
    price."""
    from ..analysis.registry import get_decomposer
    from ..delta_opt.decompose import Decomposition

    tl = jax.tree.leaves(state)[0].shape[0]
    safe = jnp.clip(idx, 0, tl - 1)
    rows = jax.tree.map(lambda x: x[safe], state)
    lane_ok = idx >= 0
    d = cohort_deltas(kind, rows, bases)
    valid = d.valid & lane_ok[:, None]
    d = Decomposition(
        lanes=jax.tree.map(
            lambda x: jnp.where(
                valid.reshape(valid.shape + (1,) * (x.ndim - 2)),
                x, jnp.zeros_like(x),
            ),
            d.lanes,
        ),
        valid=valid,
        residual=d.residual,
    )
    base_rows, _ = get_decomposer(kind).split(bases)
    wire = cohort_wire_encode(d, jax.tree.leaves(base_rows)[0])
    pb = jnp.where(lane_ok, cohort_push_bytes(wire), 0.0)
    return wire, pb


def mesh_fanout_push(
    state,
    bases,
    idx,
    mesh: Mesh,
    *,
    kind: str = "orswot",
    weights=None,
    telemetry: bool = False,
):
    """Compute one dispatch of cohort δ pushes against a tenant
    superblock, sharded over the replica mesh axis. Returns
    ``(wire, push_bytes[B])`` — or ``(wire, push_bytes, Telemetry)``
    with ``telemetry=True`` (module docstring)."""
    sb_ops.tenant_kind(kind)  # fail fast on an unregistered kind
    p = mesh.shape[REPLICA_AXIS]
    idx = jnp.asarray(idx, jnp.int32)
    _validate(state, bases, idx, p)
    weights = (
        jnp.ones(idx.shape, jnp.float32) if weights is None
        else jnp.asarray(weights, jnp.float32)
    )

    row_spec = P(REPLICA_AXIS)

    def build():
        # The wire's pytree structure (for out_specs): trace the core
        # abstractly once per compiled program (the cache key holds
        # every shape and dtype the trace reads) — scalar leaves
        # (nnz/chk) replicate, batched leaves shard over the replica
        # axis like the lanes they price.
        metrics.count("fanout_push.spec_builds")
        wire_struct, _ = jax.eval_shape(
            lambda s, b, i: _local_push(kind, s, b, i), state, bases, idx
        )
        wire_spec = jax.tree.map(
            lambda s: row_spec if s.ndim else P(), wire_struct
        )

        def body(state, bases, idx, wts):
            wire, pb = _local_push(kind, state, bases, idx)
            wire = wire._replace(
                nnz=lax.psum(wire.nnz, REPLICA_AXIS),
                chk=lax.psum(wire.chk, REPLICA_AXIS),
            )
            if not telemetry:
                return wire, pb
            lane_ok = idx >= 0
            h = obs_hist.observe_vec(obs_hist.zeros(), pb, lane_ok)
            tel = tele.zeros()._replace(
                cohorts_per_dispatch=lax.psum(
                    jnp.sum(lane_ok, dtype=jnp.uint32), REPLICA_AXIS
                ),
                # Price every subscriber DELIVERY: one cohort payload
                # fans out to `wts` clients.
                delta_push_bytes=lax.psum(
                    jnp.sum(pb * wts, dtype=jnp.float32), REPLICA_AXIS
                ),
                hist_push_bytes=obs_hist.psum(h, REPLICA_AXIS),
            )
            return wire, pb, tel

        in_specs = (
            jax.tree.map(lambda _: row_spec, state),
            jax.tree.map(lambda _: row_spec, bases),
            row_spec,
            row_spec,
        )
        out_specs = (wire_spec, row_spec) + (
            (tele.specs(),) if telemetry else ()
        )
        return jax.shard_map(
            body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
        )

    fn = _cached(
        "fanout_push", (state, bases, idx, weights), mesh, build, kind,
        telemetry,
    )
    t0 = time.perf_counter()
    out = fn(state, bases, idx, weights)
    if telemetry:
        jax.block_until_ready(out)
        wire, pb, tel = out
        tel = tele.time_dispatch(tel, time.perf_counter() - t0)
        return wire, pb, tel
    return out


# ---- static-analysis registration (crdt_tpu.analysis) --------------------

def _example(mesh: Mesh, kind: str = "orswot"):
    p = mesh.shape[REPLICA_AXIS]
    caps = dict(n_elems=4, n_actors=2, deferred_cap=2)
    tk = sb_ops.tenant_kind(kind)
    t, b = p * 4, p * 2
    state = tk.empty(**caps, batch=(t,))
    bases = tk.empty(**caps, batch=(b,))
    import numpy as np

    idx = jnp.asarray(np.tile(np.arange(b // p, dtype=np.int32), p))
    # Weights ride as a positional example arg so the jit-lint/cost
    # gates trace the cached fn with the exact calling convention.
    return state, bases, idx, jnp.ones(idx.shape, jnp.float32)


def _register() -> None:
    from ..analysis.registry import register_entry_point

    register_entry_point(
        "mesh_fanout_push",
        kind="fanout_push",
        make_args=_example,
        invoke=lambda mesh, args: mesh_fanout_push(
            args[0], args[1], args[2], mesh, weights=args[3]
        ),
        n_donated=0,
    )


_register()

__all__ = ["mesh_fanout_push"]
