"""Shared cohort-batched pipeline stages for the sync and async engines.

`FleetEngine.run_round` (synchronous barrier) and
`AsyncFleetEngine.run_window` (virtual-time arrival windows) run the same
upload pipeline over a stacked cohort:

  local SGD -> delta -> [DGC accumulate+sparsify] -> [ALDP clip+noise]
            -> rebuild node models -> cloud-side accuracy

Only the aggregation differs (barrier masked-mean vs staleness-aware
arrival-order mixing), so the stages live here as module-level functions
parameterized by `FleetConfig` with a pluggable backend: "reference"
(pure-jnp `accumulator`/`aldp`, bit-compatible with the sequential
trainer) or "pallas" (the node-batched fused `sparsify`/`ldp_noise`
kernels).

Every stage here is *shard-oblivious*: all math is per-node along the
leading axis with no cross-node reduction, so the mesh-sharded engines
(`fleet.mesh.FleetMesh`) call the very same functions inside `shard_map`
on each device's node/cohort block — only detection thresholds and
aggregation (which do cross nodes) pick up collectives, and those live in
the engines' sharded round/window builders, not here. `detect_masked`
below is the one cross-node stage: sharded callers hand it the
`all_gather`-ed accuracy set.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import accumulator as accum
from ..core import aldp, detection

# The named scopes of a round's (or window's) stages.  The engines wrap
# each stage's ops in its scope, so every HLO op it lowers to carries the
# scope in its `op_name` metadata and a profile's device ops can be put to
# the stage that issued them.  Scopes change metadata only, never the ops.
LOCAL_SGD = "fleet.local_sgd"       # cohort gather, local SGD, deltas
BATCH_SELECT = "batch_select"       # a local step's minibatch, in LOCAL_SGD
UPLOAD = "fleet.upload"             # attack, DGC + ALDP, residual scatter
CLOUD_SCORE = "fleet.cloud_score"   # rebuild + cloud accuracy, Alg. 2
FOLD = "fleet.fold"                 # aggregation / window fold, mix, trust
EVALUATE = "fleet.evaluate"         # the global model's test-set pass


def scoped(name: str, fn):
    """``fn`` with every op it traces under the named scope ``name``."""

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)

    return run


# ---------------------------------------------------------------------------
# stage: node-local minibatch SGD
# ---------------------------------------------------------------------------

def select_rows(x, idx):
    """``x[idx]`` for a shard ``x`` of M rows and a vector ``idx`` in
    [0, M), taken as ``one_hot(idx, M) @ x`` on the MXU: a vmapped gather
    of rows runs element by element on the chip.  The product is at
    ``Precision.HIGHEST``, which on the TPU splits an f32 operand into
    three bf16 parts: each part times 1 and every ``0 · x`` is exact, so
    for finite ``x`` the rows are the gather's bit for bit.  At the
    default precision the TPU would round ``x`` to bf16.

    The rows are flattened to M x D for the product.  Contracted in place
    instead, the v5e compiler writes them as bf16 straight into the
    default-precision convolutions that read them, which read an f32
    operand more finely than that: trained params then lie farther from
    a ``HIGHEST`` step.  Flattened, they stay f32 and reach the
    convolutions as the gather's did, through one layout copy."""
    m = x.shape[0]
    sel = (idx[:, None] == jnp.arange(m)).astype(x.dtype)
    rows = jnp.einsum("bm,md->bd", sel, x.reshape(m, -1),
                      precision=jax.lax.Precision.HIGHEST)
    return rows.reshape(idx.shape + x.shape[1:])


def make_local_train(loss_fn, local_steps: int, lr: float, batch_size: int):
    """Single-node local SGD body; identical math/key-use to the sequential
    trainer's `_local_train_impl` (bounds from `size`, not the padded shard
    length). The sync engine vmaps it with the global params broadcast
    (`in_axes=(None, ...)`); the async engine with per-node dispatched
    params (`in_axes=(0, ...)`).

    Each step's minibatch comes from `select_rows`, under the scope
    `BATCH_SELECT`: a one-hot product at ``Precision.HIGHEST``, the only
    precision at which the TPU keeps f32 rows whole, so the minibatch is
    bit-identical to the gather ``x[idx]``.  The labels are gathered."""

    def local_train(params, x, y, size, key):
        def body(p, k):
            idx = jax.random.randint(k, (batch_size,), 0, size)
            with jax.named_scope(BATCH_SELECT):
                batch = {"x": select_rows(x, idx), "y": y[idx]}
            g = jax.grad(lambda pp: loss_fn(pp, batch)[0])(p)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), None

        keys = jax.random.split(key, local_steps)
        p, _ = jax.lax.scan(body, params, keys)
        return p

    return local_train


# ---------------------------------------------------------------------------
# stage: upload pipeline (DGC sparsify -> ALDP), cohort-batched
# ---------------------------------------------------------------------------

def upload_pipeline(cfg, deltas, residuals_c, k2s, need_nnz: bool = False):
    """[DGC accumulate+sparsify] -> [ALDP clip+noise] over a stacked cohort.

    `cfg` needs `.sparsify_ratio`, `.sigma`, `.clip_s`, `.backend`.
    Returns (uploaded deltas, updated cohort residuals, per-node nonzero
    counts or None).  ``need_nnz`` gates the count so analytic runs (no
    `repro.net` attached) pay nothing for it.

    The counts are taken *post-sparsify, pre-noise*: they are the sparse
    coordinate set the node uploads — in the deployed system ALDP noise is
    added only to the transmitted (kept) values, so the wire message stays
    sparse.  Note the simulation-side caveat: this reference pipeline
    (inherited from the seed implementation, parity-pinned) applies the
    noise to *every* coordinate of the delta, so the update the cloud
    aggregates is denser than the priced wire message — the byte counts
    model the intended wire, not the reference pipeline's dense-noise
    artifact."""
    if cfg.backend == "pallas":
        return _upload_pipeline_fused(cfg, deltas, residuals_c, k2s,
                                      need_nnz)
    if cfg.sparsify_ratio < 1.0:
        deltas, residuals_c, _ = jax.vmap(
            lambda r, d: accum.accumulate_and_sparsify(
                r, d, cfg.sparsify_ratio))(residuals_c, deltas)
    nnz = count_upload_nnz(deltas, cfg.backend) if need_nnz else None
    if cfg.sigma > 0.0:
        deltas = jax.vmap(
            lambda d, k: aldp.aldp_perturb(d, k, cfg.sigma,
                                           cfg.clip_s)[0])(deltas, k2s)
    return deltas, residuals_c, nnz


def _upload_pipeline_fused(cfg, deltas, residuals_c, k2s, need_nnz: bool):
    """The pallas backend's upload pipeline: one fused megakernel launch
    (`kernels.upload_fused`) over the flattened cohort instead of the
    per-stage dispatch chain.  The two whole-tensor reductions the kernel
    cannot fuse past (per-leaf DGC quantile threshold; post-sparsify L2
    clip norm) run here as a single jnp pre-pass over `combined`."""
    from ..kernels import upload_fused as uf

    do_sparsify = cfg.sparsify_ratio < 1.0
    apply_ldp = cfg.sigma > 0.0
    if not (do_sparsify or apply_ldp):
        # nothing to compute per element: skip the identity kernel (and,
        # without nnz, the flatten too)
        nnz = count_upload_nnz(deltas, "pallas") if need_nnz else None
        return deltas, residuals_c, nnz
    layout = cohort_layout(deltas)
    flat_d = layout.flatten(deltas)
    thresholds = flat_r = comb = None
    if do_sparsify:
        flat_r = layout.flatten(residuals_c)
        comb = flat_d + flat_r
        thresholds = jnp.stack(
            [jax.vmap(lambda v: accum.leaf_threshold(
                v, cfg.sparsify_ratio))(comb[:, off:off + size])
             for off, size in zip(layout.offsets, layout.sizes)], axis=1)
    seeds = scales = None
    if apply_ldp:
        if do_sparsify:
            thr_elem = uf.spread_thresholds(thresholds, layout.offsets,
                                            layout.total)
            sp = jnp.where(jnp.abs(comb) >= thr_elem, comb, 0.0)
        else:
            sp = flat_d
        norms = jnp.sqrt(jnp.sum(jnp.square(sp), axis=1))
        scales = 1.0 / jnp.maximum(1.0, norms / cfg.clip_s)
        seeds = node_noise_seeds(k2s)
    up, newr, nnz = uf.upload_fused_fleet(
        flat_d, flat_r, thresholds, seeds, scales, cfg.sigma, cfg.clip_s,
        boundaries=layout.offsets, need_nnz=need_nnz)
    deltas = layout.unflatten(up)
    if do_sparsify:
        residuals_c = layout.unflatten_like(newr, residuals_c)
    return deltas, residuals_c, nnz


def node_noise_seeds(k2s) -> jnp.ndarray:
    """Node-distinct int32 noise seeds folded from the per-node PRNG keys —
    shared by the fused and unfused pallas ALDP paths."""
    raw = k2s
    if jnp.issubdtype(k2s.dtype, jax.dtypes.prng_key):   # new-style typed keys
        raw = jax.random.key_data(k2s)
    return (raw[:, 0] ^ raw[:, -1]).astype(jnp.int32)


def count_upload_nnz(deltas, backend: str = "reference") -> jnp.ndarray:
    """Per-node nonzero count of a stacked upload tree — the wire quantity
    `repro.net`'s sparse codecs price.  The pallas path shares
    `net.codecs.count_nnz`'s fused `kernels.wire_bytes.nnz_fleet` kernel
    over the flattened cohort; the reference path reduces per leaf (no
    flatten/concat materialization)."""
    if backend == "pallas":
        from ..net.codecs import count_nnz
        flat = cohort_layout(deltas).flatten(deltas)
        return count_nnz(flat, backend="pallas")
    c = jax.tree.leaves(deltas)[0].shape[0]
    return sum(jnp.sum(d.reshape(c, -1) != 0, axis=1).astype(jnp.int32)
               for d in jax.tree.leaves(deltas))


def rebuild_and_evaluate(acc_fn, start_params, deltas, cloud_x, cloud_y):
    """Rebuild every node's uploaded model ω_new = ω_start + Δ and score it
    on the cloud testing dataset (§5.4). `start_params` is either the global
    model (sync: leaves without node axis, broadcast) or the stacked
    dispatched params (async: leading node axis)."""
    broadcast = (jax.tree.leaves(deltas)[0].ndim
                 > jax.tree.leaves(start_params)[0].ndim)
    if broadcast:       # start_params has no node axis: broadcast it
        omegas = jax.tree.map(lambda g, d: g[None].astype(d.dtype) + d,
                              start_params, deltas)
    else:
        omegas = jax.tree.map(lambda g, d: g.astype(d.dtype) + d,
                              start_params, deltas)
    accs = jax.vmap(lambda p: acc_fn(p, cloud_x, cloud_y))(omegas)
    return omegas, accs


# ---------------------------------------------------------------------------
# stage: masked detection (Alg. 2 over a partially-valid cohort)
# ---------------------------------------------------------------------------

def detect_masked(accs: jnp.ndarray, valid: jnp.ndarray, s: float
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Alg. 2 with padded slots excluded: threshold is the top-s percentile
    of the *valid* accuracies; reduces to `detection.detect` when all slots
    are valid."""
    masked = jnp.where(valid, accs.astype(jnp.float32), jnp.nan)
    thr = jnp.nanpercentile(masked, s)
    mask = (accs > thr) & valid
    mask = jnp.where(mask.any(), mask, (accs >= thr) & valid)
    return mask, thr


# ---------------------------------------------------------------------------
# stage: the adversary zoo's delta-level attacks
#
# Data-level attacks (label_flip, backdoor, the sybils' shared shard) are
# baked into the shards by `data.federated`; what remains engine-side is
# per-node row scaling of the uploaded deltas — sybil boosting and the
# adaptive attacker's detection-aware throttle — plus the DDoS flood count
# the host feeds to `NetSim.draw`.  All of it is elementwise along the
# leading node axis (no cross-node reduction), so the stage runs unchanged
# inside the mesh engines' shard_map: shard-oblivious by construction.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttackPlan:
    """Engine-side view of an `api.AttackMix` + the materialized malicious
    ids: which rows are adversarial and how their uploads misbehave."""
    kind: str                       # label_flip|sybil|backdoor|adaptive|ddos
    malicious: np.ndarray           # (N,) bool host-side membership
    sybil_boost: float = 3.0
    adapt_poison_scale: float = 0.5
    ddos_uploads: int = 4

    @classmethod
    def from_spec(cls, attack, n_nodes: int, malicious_ids) -> "AttackPlan":
        mal = np.zeros(int(n_nodes), bool)
        mal[np.asarray(list(malicious_ids), int)] = True
        return cls(kind=attack.kind, malicious=mal,
                   sybil_boost=float(attack.sybil_boost),
                   adapt_poison_scale=float(attack.adapt_poison_scale),
                   ddos_uploads=int(attack.ddos_uploads))

    @property
    def n_malicious(self) -> int:
        return int(self.malicious.sum())

    @property
    def needs_throttle(self) -> bool:
        """Does this attack carry device-side state (`FleetState.throttle`)?"""
        return self.kind == "adaptive"

    @property
    def flood_uploads(self) -> int:
        """Extra concurrent flows the host injects into `NetSim.draw`'s
        shared-uplink contention each round/window (the DDoS attack)."""
        return (self.n_malicious * self.ddos_uploads
                if self.kind == "ddos" else 0)

    def mask(self, n_total: int = None) -> jnp.ndarray:
        """(n_total,) bool device mask, padded False (mesh pad rows are
        honest dummies)."""
        m = self.malicious
        if n_total is not None and n_total > m.shape[0]:
            m = np.concatenate([m, np.zeros(n_total - m.shape[0], bool)])
        return jnp.asarray(m)


def scale_node_rows(tree, scale: jnp.ndarray):
    """Multiply every leaf's node rows by the (C,) per-node scale."""
    return jax.tree.map(
        lambda x: (x * scale.reshape((-1,) + (1,) * (x.ndim - 1))
                   .astype(x.dtype)), tree)


def make_delta_attack(plan):
    """The pluggable delta-level attack stage, or None when the attack
    does not touch uploads.  Returns stage(deltas, mal_c, throttle_c) —
    ``mal_c`` the cohort's malicious mask, ``throttle_c`` the adaptive
    attacker's per-node poison scale (ignored by sybil)."""
    if plan is None or plan.kind not in ("sybil", "adaptive"):
        return None
    if plan.kind == "sybil":
        boost = float(plan.sybil_boost)

        def stage(deltas, mal_c, throttle_c=None):
            return scale_node_rows(
                deltas, jnp.where(mal_c, boost, 1.0).astype(jnp.float32))
    else:
        def stage(deltas, mal_c, throttle_c):
            return scale_node_rows(
                deltas, jnp.where(mal_c, throttle_c, 1.0)
                .astype(jnp.float32))
    return stage


def adaptive_throttle_update(throttle: jnp.ndarray, rejected: jnp.ndarray,
                             seen: jnp.ndarray, scale: float) -> jnp.ndarray:
    """The detection-aware attacker's control law, per participating node:
    caught ⇒ back the poison off (× ``scale``); accepted ⇒ creep back up
    (× 1.1, capped at full strength).  Non-participants keep their state.
    Applied to malicious rows only (honest rows carry throttle 1.0 and are
    never scaled)."""
    upd = jnp.where(rejected, throttle * float(scale),
                    jnp.minimum(1.0, throttle * 1.1))
    return jnp.where(seen, upd, throttle)


# ---------------------------------------------------------------------------
# cohort flat layout (cached) + the pallas-backed cohort upload pipeline
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CohortLayout:
    """Static flat layout of a stacked cohort tree: leaf order, shapes,
    dtypes and start offsets in the concatenated (C, P) f32 view.  Built
    once per (treedef, shapes, dtypes) via `cohort_layout` — the flatten /
    unflatten closures and leaf boundaries used to be rebuilt on every
    trace by each pipeline stage separately; now every pallas stage (the
    fused pipeline, the unfused ALDP chain, nnz counting, the window fold)
    shares one cached layout."""
    treedef: object
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[np.dtype, ...]
    sizes: Tuple[int, ...]
    offsets: Tuple[int, ...]        # start of each leaf in the flat axis
    total: int                      # P — flattened per-node element count

    def flatten(self, tree) -> jnp.ndarray:
        """Stacked tree with leading cohort axis -> (C, P) f32."""
        return jnp.concatenate(
            [l.reshape(l.shape[0], -1).astype(jnp.float32)
             for l in jax.tree.leaves(tree)], axis=1)

    def flatten_one(self, tree) -> jnp.ndarray:
        """Unbatched tree (no cohort axis) -> (P,) f32, same leaf order."""
        return jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                                for l in jax.tree.leaves(tree)])

    def unflatten(self, flat: jnp.ndarray):
        out = [flat[:, o:o + s].reshape((flat.shape[0],) + shape).astype(dt)
               for shape, dt, s, o in zip(self.shapes, self.dtypes,
                                          self.sizes, self.offsets)]
        return jax.tree.unflatten(self.treedef, out)

    def unflatten_one(self, flat: jnp.ndarray):
        out = [flat[o:o + s].reshape(shape).astype(dt)
               for shape, dt, s, o in zip(self.shapes, self.dtypes,
                                          self.sizes, self.offsets)]
        return jax.tree.unflatten(self.treedef, out)

    def unflatten_like(self, flat: jnp.ndarray, tree):
        """Unflatten casting to `tree`'s leaf dtypes (e.g. residual trees,
        whose dtypes may differ from the deltas this layout was built on)."""
        leaves = jax.tree.leaves(tree)
        out = [flat[:, o:o + s].reshape((flat.shape[0],) + shape)
               .astype(l.dtype)
               for shape, l, s, o in zip(self.shapes, leaves, self.sizes,
                                         self.offsets)]
        return jax.tree.unflatten(self.treedef, out)


@functools.lru_cache(maxsize=128)
def _cohort_layout(treedef, shapes, dtypes) -> CohortLayout:
    sizes = tuple(int(np.prod(s)) for s in shapes)
    offsets = tuple(int(o) for o in np.concatenate(
        [[0], np.cumsum(sizes)[:-1]]))
    return CohortLayout(treedef, shapes, dtypes, sizes, offsets,
                        int(sum(sizes)))


def cohort_layout(tree) -> CohortLayout:
    """Cached `CohortLayout` for a stacked tree (leading cohort axis)."""
    leaves, treedef = jax.tree.flatten(tree)
    return _cohort_layout(treedef,
                          tuple(tuple(l.shape[1:]) for l in leaves),
                          tuple(np.dtype(l.dtype) for l in leaves))


def flatten_cohort(tree):
    """Stacked tree with leading cohort axis -> ((C, P) flat, unflatten)."""
    layout = cohort_layout(tree)
    return layout.flatten(tree), layout.unflatten


def sparsify_pallas_cohort(deltas, residuals, ratio: float):
    """Per-leaf DGC split via the node-batched `sparsify_fleet` kernel —
    same per-leaf quantile threshold rule as `accum.accumulate_and_sparsify`,
    but one kernel launch per leaf for the whole cohort."""
    from ..kernels.sparsify import sparsify_fleet

    def one_leaf(d, r):
        c = d.shape[0]
        df = d.reshape(c, -1).astype(jnp.float32)
        rf = r.reshape(c, -1).astype(jnp.float32)
        comb = df + rf
        thr = jax.vmap(lambda v: accum.leaf_threshold(v, ratio))(comb)
        up, newr = sparsify_fleet(df, rf, thr)
        return up.reshape(d.shape).astype(d.dtype), newr.reshape(r.shape)

    pairs = jax.tree.map(one_leaf, deltas, residuals)
    up = jax.tree.map(lambda p: p[0], pairs,
                      is_leaf=lambda x: isinstance(x, tuple))
    newr = jax.tree.map(lambda p: p[1], pairs,
                        is_leaf=lambda x: isinstance(x, tuple))
    return up, newr


def aldp_pallas_cohort(deltas, k2s, sigma: float, clip_s: float):
    """Cohort ALDP via the node-batched `ldp_perturb_fleet` kernel: whole-
    delta clip scale per node, in-kernel Gaussian noise (node-distinct
    seeds folded from the per-node PRNG keys).  Kept as the unfused
    comparator for `kernels.upload_fused` (benchmarks + property tests);
    the engines' pallas backend runs the fused pipeline."""
    from ..kernels.ldp_noise import ldp_perturb_fleet

    layout = cohort_layout(deltas)
    flat = layout.flatten(deltas)
    norms = jnp.sqrt(jnp.sum(jnp.square(flat), axis=1))
    scales = 1.0 / jnp.maximum(1.0, norms / clip_s)
    out = ldp_perturb_fleet(flat, node_noise_seeds(k2s), scales, sigma,
                            clip_s)
    return layout.unflatten(out)


# ---------------------------------------------------------------------------
# construction + wire-format accounting shared by both engines
# ---------------------------------------------------------------------------

DEFAULT_BANDWIDTH_BPS = 12.5e6      # 100 Mbit/s edge uplink


def init_engine_common(init_params, node_data, test_data, cloud_test,
                       profile):
    """The setup both engines share: coerce per-node shards to `FleetData`,
    move eval sets to device, default the system profile, count params.

    Returns (data, n_nodes, test_data, cloud_test, profile, n_params)."""
    from .engine import NodeProfile       # deferred: engine imports stages
    from .state import FleetData

    data = (node_data if isinstance(node_data, FleetData)
            else FleetData.from_node_data(node_data))
    n_nodes = data.n_nodes
    test = (jnp.asarray(test_data[0]), jnp.asarray(test_data[1]))
    cloud = (jnp.asarray(cloud_test[0]), jnp.asarray(cloud_test[1]))
    profile = profile or NodeProfile(
        compute_s=np.ones(n_nodes),
        bandwidth_bps=np.full(n_nodes, DEFAULT_BANDWIDTH_BPS))
    n_params = sum(x.size for x in jax.tree.leaves(init_params))
    return data, n_nodes, test, cloud, profile, n_params


def bytes_per_node(n_params: int, sparsify_ratio: float) -> float:
    """Analytic upload size per node: dense f32 values, or (value, index)
    pairs for a sparsified upload — the shared `repro.net` fallback
    (`accumulator.upload_bytes` delegates to the same helper, pinned by
    tests/test_net.py).  Byte-accurate accounting lives in `repro.net`."""
    from ..net.codecs import analytic_upload_bytes
    return analytic_upload_bytes(n_params, sparsify_ratio)
