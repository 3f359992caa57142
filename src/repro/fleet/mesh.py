"""FleetMesh: shard the stacked node axis of a fleet across local devices.

Both fleet engines keep every per-node quantity — residual pytrees, data
shards, dispatched models, virtual clocks — stacked along a leading node
axis (`state.FleetState` / `state.FleetData`). On one device that axis caps
fleet size by memory, not math. `FleetMesh` places those arrays on a 1-D
``Mesh(("nodes",))`` with a `NamedSharding` over the node axis (the same
Mesh/NamedSharding/PartitionSpec conventions as `repro.sharding.ctx`) and
the engines run their per-round / per-window programs under `shard_map`:

  * the embarrassingly node-parallel stages (local SGD, DGC sparsify, ALDP,
    per-node cloud evaluation) run on each device's shard of nodes;
  * the small cross-node steps (detection threshold, masked-mean aggregate,
    the async sequential Eq. (6)/`mix_stale` fold and its accuracy ring)
    see globally gathered values via `psum`/`all_gather` collectives and
    run replicated, so their results are identical on every device.

The node axis is padded up to a multiple of the device count
(`FleetMesh.padded`); padding rows carry a size-1 dummy shard, never
participate (their valid/proc masks are False) and never arrive
(`next_arrival = +inf`).

This module also hosts the collective primitives the sharded round/window
programs are written with: `my_block` (slice a replicated array down to this
device's block), `gather_rows` (pull an arbitrary global-index cohort out of
a node-sharded array, replicated everywhere via a masked `psum`) and
`scatter_rows` (write cohort rows back into the owner device's shard).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


class FleetMesh:
    """A 1-D device mesh over the fleet's node axis.

    Args:
      devices: the devices to shard over (defaults to all local devices).
      axis: mesh axis name (default ``"nodes"``).
    """

    def __init__(self, devices: Optional[Sequence] = None,
                 axis: str = "nodes"):
        devices = list(devices) if devices is not None else jax.devices()
        if not devices:
            raise ValueError("FleetMesh needs at least one device")
        self.axis = axis
        self.mesh = Mesh(np.asarray(devices), (axis,))

    @classmethod
    def create(cls, n_devices: Optional[int] = None,
               axis: str = "nodes") -> "FleetMesh":
        """Mesh over the first `n_devices` local devices (None = all).

        Raises with a clear message when the host exposes fewer devices
        than requested — use ``--xla_force_host_platform_device_count`` to
        fake a multi-device CPU host.
        """
        avail = jax.devices()
        if n_devices is None:
            n_devices = len(avail)
        if n_devices > len(avail):
            raise ValueError(
                f"FleetMesh over {n_devices} devices requested but only "
                f"{len(avail)} visible; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={n_devices} "
                f"before importing jax to fake a multi-device host")
        return cls(avail[:n_devices], axis=axis)

    @property
    def n_devices(self) -> int:
        return self.mesh.shape[self.axis]

    def padded(self, n_nodes: int) -> int:
        """Node count rounded up to a shard multiple."""
        d = self.n_devices
        return ((n_nodes + d - 1) // d) * d

    # -- placement ----------------------------------------------------------
    def spec_nodes(self) -> P:
        return P(self.axis)

    def spec_replicated(self) -> P:
        return P()

    def put_nodes(self, tree):
        """Place every leaf's leading (node) axis across the mesh. The axis
        length must already be a shard multiple (see :meth:`padded`)."""
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def put_replicated(self, tree):
        sh = NamedSharding(self.mesh, P())
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    # -- program wrapper ----------------------------------------------------
    def shard_map(self, f, in_specs, out_specs):
        """`shard_map` bound to this mesh. Replication checking is disabled:
        the fleet programs mix replicated PRNG-chain scans and collectives,
        and their replicated outputs are established by `psum`/`all_gather`
        by construction."""
        return jax.shard_map(f, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# collectives used inside sharded round/window programs
#
# Every helper takes the mesh axis name plus the per-device block size B of
# the node-sharded operand (global padded rows = B * n_devices, device d
# owning the contiguous rows [d*B, (d+1)*B) — NamedSharding's layout for a
# 1-D mesh).
# ---------------------------------------------------------------------------

def my_block(x, axis: str, n_devices: int):
    """Slice this device's contiguous block out of a replicated array whose
    leading axis is a multiple of the device count (replicated -> sharded)."""
    b = x.shape[0] // n_devices
    return jax.lax.dynamic_slice_in_dim(x, jax.lax.axis_index(axis) * b, b)


def my_block_tree(tree, axis: str, n_devices: int):
    return jax.tree.map(lambda x: my_block(x, axis, n_devices), tree)


def gather_rows(x_local, idx, axis: str, block: int):
    """Gather global rows `idx` from a node-sharded array; result replicated.

    Each device contributes the rows it owns (zeros elsewhere) and a `psum`
    over the mesh reconstructs the full cohort on every device — exactly one
    device owns each row, so the sum is exact (no float reordering).
    """
    off = jax.lax.axis_index(axis) * block
    local = idx - off
    mine = (local >= 0) & (local < block)
    rows = jnp.take(x_local, jnp.clip(local, 0, block - 1), axis=0)
    shape = (mine.shape[0],) + (1,) * (rows.ndim - 1)
    contrib = jnp.where(mine.reshape(shape), rows,
                        jnp.zeros((), rows.dtype))
    return jax.lax.psum(contrib, axis)


def gather_rows_tree(tree_local, idx, axis: str, block: int):
    return jax.tree.map(lambda x: gather_rows(x, idx, axis, block),
                        tree_local)


def scatter_rows(x_local, idx, values, keep, axis: str, block: int):
    """Write replicated cohort rows `values` back into the node-sharded
    array: each device updates only the rows it owns; `keep` masks cohort
    slots that must not be written (padding / out-of-window). Duplicate
    global indices in `idx` must carry identical values (last write wins,
    same contract as `state.scatter_nodes`)."""
    off = jax.lax.axis_index(axis) * block
    local = idx - off
    mine = keep & (local >= 0) & (local < block)
    rows = jnp.where(mine, local, block)          # out of bounds => dropped
    return x_local.at[rows].set(values, mode="drop")


def scatter_rows_tree(tree_local, idx, values, keep, axis: str, block: int):
    return jax.tree.map(
        lambda x, v: scatter_rows(x, idx, v, keep, axis, block),
        tree_local, values)


def all_gather_tree(tree, axis: str):
    """Concatenate every leaf's sharded leading axis back to the full
    (replicated) cohort, preserving global row order (sharded -> replicated)."""
    return jax.tree.map(
        lambda x: jax.lax.all_gather(x, axis, tiled=True), tree)


class MeshStateIO:
    """Mesh-aware state ingress/egress shared by both fleet engines.

    Host classes provide ``self.mesh`` (a `FleetMesh` or None),
    ``self.n_nodes`` / ``self.n_pad``, and ``self.state`` (a `FleetState`
    with ``.residuals`` / ``.chain_key``).
    """

    def load_state(self, residuals_stacked, chain_key) -> None:
        """Adopt externally-held per-node residuals (stacked, n_nodes rows)
        and a chain key — padding/placing them onto the mesh when sharded."""
        import dataclasses

        from .state import pad_node_axis
        if self.mesh is not None:
            residuals_stacked = self.mesh.put_nodes(
                pad_node_axis(residuals_stacked, self.n_pad))
            chain_key = self.mesh.put_replicated(chain_key)
        self.state = dataclasses.replace(
            self.state, residuals=residuals_stacked, chain_key=chain_key)

    def export_residuals(self):
        """The stacked residuals restricted to real nodes (padding dropped),
        gathered to host-addressable arrays."""
        return jax.tree.map(lambda x: jax.device_get(x[:self.n_nodes]),
                            self.state.residuals)

    def _participation_mask(self, idx, valid) -> np.ndarray:
        """(idx, valid) cohort -> per-node bool mask over the padded fleet
        (padding rows always False)."""
        up = np.zeros(self.n_pad, bool)
        up[np.asarray(idx)[np.asarray(valid)]] = True
        return up

    # -- full-state snapshot (repro.sim checkpoint/resume) ------------------
    # per-node FleetState fields (leading node axis, trimmed to real nodes
    # on export) and replicated fields; None fields are simply absent from
    # the snapshot, so sync/async engines and defense on/off variants all
    # share this one code path
    _SIM_NODE_FIELDS = ("next_arrival", "dispatched_version", "trust",
                        "throttle")
    _SIM_REP_FIELDS = ("version", "acc_ring", "acc_count")

    def export_sim_state(self) -> dict:
        """Every device-side array a bit-exact resume needs, as a flat
        host-side dict of numpy arrays/pytrees (padding rows dropped)."""
        st = self.state
        n = self.n_nodes

        def trim(tree):
            return jax.tree.map(
                lambda x: np.asarray(jax.device_get(x))[:n], tree)

        out = {
            "params": jax.tree.map(lambda x: np.asarray(jax.device_get(x)),
                                   self.params),
            "chain_key": _key_data(st.chain_key),
            "residuals": trim(st.residuals),
        }
        if st.dispatched is not None:
            out["dispatched"] = trim(st.dispatched)
        for name in self._SIM_NODE_FIELDS:
            v = getattr(st, name)
            if v is not None:
                out[name] = np.asarray(jax.device_get(v))[:n]
        for name in self._SIM_REP_FIELDS:
            v = getattr(st, name)
            if v is not None:
                out[name] = np.asarray(jax.device_get(v))
        return out

    def load_sim_state(self, tree: dict) -> None:
        """Restore an `export_sim_state` snapshot into this engine.

        The engine must be freshly constructed for the same spec shape
        (same node count / defense fields): real-node rows are overwritten,
        padding rows keep their init values (+inf arrival clocks, dummy
        data) — they never participate, so the restored run is bit-exact.
        Fields present in the snapshot but absent on this engine (or vice
        versa, e.g. trust rings after a defense-onset event) keep their
        fresh init — exactly the semantics a mid-run spec mutation wants.
        """
        import dataclasses
        st = self.state
        n = self.n_nodes
        if self.mesh is not None:
            place_nodes = self.mesh.put_nodes
            place_rep = self.mesh.put_replicated
        else:
            def place_nodes(t):
                return jax.tree.map(jnp.asarray, t)
            place_rep = place_nodes

        def rows(cur, new):
            host = np.array(jax.device_get(cur))    # padding rows survive
            host[:n] = np.asarray(new)
            return host

        updates = {
            "residuals": place_nodes(
                jax.tree.map(rows, st.residuals, tree["residuals"])),
            "chain_key": place_rep(_key_like(st.chain_key,
                                             tree["chain_key"])),
        }
        if st.dispatched is not None and "dispatched" in tree:
            updates["dispatched"] = place_nodes(
                jax.tree.map(rows, st.dispatched, tree["dispatched"]))
        for name in self._SIM_NODE_FIELDS:
            cur = getattr(st, name)
            if cur is not None and name in tree:
                updates[name] = place_nodes(rows(cur, tree[name]))
        for name in self._SIM_REP_FIELDS:
            cur = getattr(st, name)
            if cur is not None and name in tree:
                updates[name] = place_rep(np.asarray(tree[name]))
        self.state = dataclasses.replace(st, **updates)
        self.params = place_rep(jax.tree.map(jnp.asarray, tree["params"]))


def _key_data(key):
    """A PRNG chain key as raw host bits (typed keys unwrapped)."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(jax.device_get(key))


def _key_like(cur, data):
    """Raw key bits back to the kind of key the engine carries."""
    data = jnp.asarray(np.asarray(data))
    if jnp.issubdtype(cur.dtype, jax.dtypes.prng_key):
        return jax.random.wrap_key_data(data, impl=jax.random.key_impl(cur))
    return data
