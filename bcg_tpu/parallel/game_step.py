"""SPMD game round step: one-agent-per-chip message exchange and vote
tally as XLA collectives.

This is the TPU-native form of the A2A broadcast/receive/vote phases
(reference ``a2a_sim.py`` + ``byzantine_consensus.py:251-398``): per-agent
(value, vote) scalars live sharded over the ``dp`` mesh axis; "broadcast
to neighbours" is one ``all_gather`` over ICI followed by a static
topology mask; vote counting and consensus checks are pure array math on
the gathered tensors.  Semantics match the host game exactly (tested
against it) — this path exists for the 16/64-agent one-agent-per-chip
scale sweeps (BASELINE.json configs 4-5) where host-side Python routing
would serialize the round.

Value conventions: ``value < 0`` encodes abstention (no proposal);
votes are ints {1: stop, 0: continue, -1: abstain}.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _masked_receive(all_vals: jax.Array, mask_rows: jax.Array) -> jax.Array:
    """The exchange body shared by the shard_map delivery paths: row i
    of the result holds agent j's value iff ``mask_rows[i, j]`` AND j
    proposed (``all_vals[j] >= 0``), else -1.  ``all_vals`` is the full
    [n] value vector, ``mask_rows`` the (possibly sharded) receiver-mask
    rows."""
    return jnp.where(mask_rows & (all_vals >= 0)[None, :], all_vals[None, :], -1)


def _masked_receive_matrix(
    proposals: jax.Array, mask_rows: jax.Array
) -> jax.Array:
    """Per-receiver generalization of :func:`_masked_receive`:
    ``proposals[i, j]`` is the value sender j addressed TO receiver i
    (equivocating senders put different values in different rows; a
    broadcasting sender's column is constant).  Row i of the result
    holds that value iff ``mask_rows[i, j]`` AND the sender proposed
    (``proposals[i, j] >= 0``), else -1."""
    return jnp.where(mask_rows & (proposals >= 0), proposals, -1)


def exchange_values(
    values: jax.Array,        # [n] int32, -1 = abstain, sharded over dp
    neighbor_mask: jax.Array, # [n, n] bool (static topology)
    mesh: Mesh,
    axis_name: str = "dp",
) -> jax.Array:
    """Neighbour-masked all-gather: returns [n, n] where row i holds
    agent j's value if j is i's neighbour AND j proposed, else -1."""

    def body(local_vals, mask_rows):
        all_vals = jax.lax.all_gather(local_vals, axis_name, tiled=True)  # [n]
        return _masked_receive(all_vals, mask_rows)

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name, None)),
        out_specs=P(axis_name, None),
    )
    return f(values, neighbor_mask)


def exchange_proposals(
    proposals: jax.Array,      # [n, n] int32, [i, j] = j's value for i
    receiver_mask: jax.Array,  # [n, n] bool (static topology)
    mesh: Mesh,
    axis_name: str = "dp",
) -> jax.Array:
    """Per-receiver (equivocation-capable) form of :func:`exchange_values`:
    each sender owns a COLUMN of per-receiver values instead of one
    scalar, so the gather runs over sender columns and each shard then
    masks its own receiver rows.  With every column constant this
    returns exactly what ``exchange_values(proposals[0], mask, mesh)``
    returns (tested)."""
    n = proposals.shape[0]
    rows_per = n // mesh.shape[axis_name]

    def body(local_cols, mask_rows):
        # local_cols [n, n/dp]: this shard's sender columns; gather the
        # full matrix, then keep only this shard's receiver rows.
        all_props = jax.lax.all_gather(
            local_cols, axis_name, axis=1, tiled=True
        )
        idx = jax.lax.axis_index(axis_name)
        local_rows = jax.lax.dynamic_slice_in_dim(
            all_props, idx * rows_per, rows_per, axis=0
        )
        return _masked_receive_matrix(local_rows, mask_rows)

    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(None, axis_name), P(axis_name, None)),
        out_specs=P(axis_name, None),
    )
    return f(proposals, receiver_mask)


def exchange_values_global(
    values_np,         # [n] int32 host array, IDENTICAL on every process
    neighbor_mask_np,  # [n, n] bool host array, identical on every process
    mesh: Mesh,
    axis_name: str = "dp",
):
    """Multi-process form of :func:`exchange_values` for meshes whose
    ``dp`` axis spans hosts (the sweep tier's cooperative one-big-game
    mode): inputs are plain host arrays — identical on every rank,
    because every rank runs the same lockstep game — distributed over
    the GLOBAL mesh via ``make_array_from_callback``, exchanged with
    the same masked all-gather, then all-gathered once more over rows
    so the output is REPLICATED: every host reads the full [n, n]
    received matrix from its addressable shard.  (A local ``jnp.
    asarray`` input would make XLA stage a cross-process transfer,
    which the CPU backend refuses and DCN makes implicit — the
    explicit global placement is the point.)  Returns a NumPy array.
    """
    import numpy as np

    values_np = np.asarray(values_np, dtype=np.int32)
    mask_np = np.asarray(neighbor_mask_np, dtype=bool)
    values = jax.make_array_from_callback(
        values_np.shape, NamedSharding(mesh, P(axis_name)),
        lambda idx: values_np[idx],
    )
    mask = jax.make_array_from_callback(
        mask_np.shape, NamedSharding(mesh, P(axis_name, None)),
        lambda idx: mask_np[idx],
    )

    def body(local_vals, mask_rows):
        all_vals = jax.lax.all_gather(local_vals, axis_name, tiled=True)
        received = _masked_receive(all_vals, mask_rows)
        # Second gather: replicate the full matrix onto every device so
        # each HOST can read the whole round locally.
        return jax.lax.all_gather(received, axis_name, tiled=True)

    # check_vma=False: the trailing all_gather DOES replicate the
    # output over dp, but shard_map's static replication checker cannot
    # see through a tiled gather to prove it.
    f = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name, None)),
        out_specs=P(None, None),
        check_vma=False,
    )
    out = f(values, mask)
    return np.asarray(out.addressable_shards[0].data)


def tally_votes(
    votes: jax.Array,   # [n] int32: 1 stop / 0 continue / -1 abstain
    mesh: Mesh,
    axis_name: str = "dp",
) -> Dict[str, jax.Array]:
    """Global stop/continue/abstain counts + 2/3 termination flag
    (reference byzantine_consensus.py:373-398 hardcodes 2n/3)."""

    def body(local_votes):
        stop = jax.lax.psum((local_votes == 1).sum(), axis_name)
        cont = jax.lax.psum((local_votes == 0).sum(), axis_name)
        abstain = jax.lax.psum((local_votes == -1).sum(), axis_name)
        total = stop + cont + abstain
        terminate = stop * 3 >= total * 2
        half = stop * 2 >= total
        return (
            jnp.broadcast_to(stop, local_votes.shape),
            jnp.broadcast_to(cont, local_votes.shape),
            jnp.broadcast_to(abstain, local_votes.shape),
            jnp.broadcast_to(terminate, local_votes.shape),
            jnp.broadcast_to(half, local_votes.shape),
        )

    f = jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis_name),),
        out_specs=(P(axis_name),) * 5,
    )
    stop, cont, abstain, term, half = f(votes)
    return {
        "stop": stop[0],
        "continue": cont[0],
        "abstain": abstain[0],
        "terminate": term[0],
        "half_stop": half[0],
    }


def check_consensus_spmd(
    values: jax.Array,          # [n] int32 current values, -1 = none
    is_byzantine: jax.Array,    # [n] bool (host-side knowledge)
    initial_values: jax.Array,  # [n] int32 honest initials, -1 for Byz
    mesh: Mesh,
    axis_name: str = "dp",
) -> Dict[str, jax.Array]:
    """Device-side consensus check with the reference's exact rule
    (byzantine_consensus.py:182-249): ALL honest agents hold the same
    value AND that value is some honest agent's initial value."""

    def body(vals, byz, inits):
        all_vals = jax.lax.all_gather(vals, axis_name, tiled=True)
        all_byz = jax.lax.all_gather(byz, axis_name, tiled=True)
        all_inits = jax.lax.all_gather(inits, axis_name, tiled=True)

        honest_valid = (~all_byz) & (all_vals >= 0)
        n_honest = honest_valid.sum()
        # Modal honest value via pairwise equality counts (O(n^2), n<=64)
        # — matches the host game's Counter().most_common (state.py:221-223).
        same = honest_valid[:, None] & honest_valid[None, :] & (
            all_vals[:, None] == all_vals[None, :]
        )
        counts = jnp.where(honest_valid, same.sum(axis=1), 0)
        modal_idx = jnp.argmax(counts)
        ref = all_vals[modal_idx]
        modal_count = counts[modal_idx]
        agreement = jnp.where(
            n_honest > 0, modal_count / jnp.maximum(n_honest, 1) * 100.0, 0.0
        )
        all_equal = (modal_count == n_honest) & (n_honest > 0)
        from_initial = ((all_inits == ref) & ~all_byz & (all_inits >= 0)).any()
        has_consensus = all_equal & from_initial
        shape = vals.shape
        return (
            jnp.broadcast_to(has_consensus, shape),
            jnp.broadcast_to(ref, shape),
            jnp.broadcast_to(agreement, shape),
        )

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(axis_name),) * 3,
    )
    ok, value, agreement = f(values, is_byzantine, initial_values)
    return {
        "has_consensus": ok[0],
        "consensus_value": value[0],
        "agreement_pct": agreement[0],
    }


def spmd_round_arrays(
    proposals: jax.Array,       # [n] int32, -1 abstain
    votes: jax.Array,           # [n] int32 {1,0,-1}
    neighbor_mask: jax.Array,   # [n, n] bool
    is_byzantine: jax.Array,
    initial_values: jax.Array,
    mesh: Mesh,
    axis_name: str = "dp",
) -> Tuple[jax.Array, Dict, Dict]:
    """One full post-decision round on device: exchange + tally + check.

    Jit-compatible; the host orchestrator converts between this and its
    object model when running at one-agent-per-chip scale."""
    received = exchange_values(proposals, neighbor_mask, mesh, axis_name)
    tally = tally_votes(votes, mesh, axis_name)
    consensus = check_consensus_spmd(
        proposals, is_byzantine, initial_values, mesh, axis_name
    )
    return received, tally, consensus


def shard_agents(n_agents: int, mesh: Mesh, axis_name: str = "dp") -> NamedSharding:
    if n_agents % mesh.shape[axis_name]:
        raise ValueError(
            f"{n_agents} agents not divisible by {axis_name}={mesh.shape[axis_name]}"
        )
    return NamedSharding(mesh, P(axis_name))
