"""Logical-axis sharding rules (the paper's C^s → physical mesh mapping).

Every model defines its parameters as a :class:`~repro.models.common.ParamSpec`
tree with *logical* axis names (``embed``, ``heads``, ``vocab``, ``experts``,
``mlp``, ``d_inner``, …).  This module is the single place those logical axes
meet *physical* mesh axes, under one axis-naming contract shared by the step
builders, the disaggregated runtime (``carve_meshes``) and the dry-run:

==========  =======================================================
mesh axis   meaning
==========  =======================================================
``pod``     slow inter-pod interconnect (DCN); outermost data axis
``data``    data parallelism / FSDP parameter sharding
``pipe``    pipeline stages (``ParallelConfig.pp``)
``seq``     context parallelism (``ParallelConfig.cp``)
``model``   tensor parallelism (``ParallelConfig.tp``)
==========  =======================================================

Per-section ``ParallelConfig(dp, tp, pp, cp)`` maps 1:1 onto a
``(data, pipe, seq, model)`` mesh via :func:`section_mesh`.

Dispatch contract (``repro.train.step.parallel_regime``): the step builders
read the ``pipe`` / ``seq`` axis sizes of the mesh they are handed and pick
the execution regime from them — ``pipe > 1`` routes the loss through
``repro.dist.pipeline.build_pp_loss``, ``seq > 1`` installs
``repro.dist.context.cp_attention`` as the model's attention
implementation, and both must agree with ``ParallelConfig.pp`` / ``.cp``
(mismatches raise instead of silently training replicated).  When the mesh
has a non-trivial ``pipe`` axis, :func:`rules_for` additionally maps the
stacked ``layers`` param dim onto it so parameters and optimizer state are
stage-partitioned at rest, matching ``build_pp_loss``'s shard_map specs.

Assignment is greedy left-to-right over a parameter's dims with two hard
invariants (property-tested): a mesh axis is never used twice in one spec,
and an axis is only assigned when the dim size divides it (divisibility
fallback → replicate).  ZeRO (:func:`zero_extend`) extends a parameter's
spec over free mesh axes for optimizer state.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.types import ArchConfig, ParallelConfig
from repro.models.common import ParamSpec, tree_map_specs

# --------------------------------------------------------------------------- #
# Axis-naming contract
# --------------------------------------------------------------------------- #
AXIS_POD = "pod"
AXIS_DATA = "data"
AXIS_PIPE = "pipe"
AXIS_SEQ = "seq"
AXIS_MODEL = "model"

#: mesh axes that carry data parallelism, outermost first
DP_AXES = (AXIS_POD, AXIS_DATA)

#: mesh axes eligible for ZeRO optimizer-state extension
ZERO_AXES = (AXIS_POD, AXIS_DATA, AXIS_MODEL)

#: logical param axis → mesh-axis candidates, tried in order
DEFAULT_RULES = {
    "embed": (AXIS_DATA,),          # FSDP: weights sharded over data
    "heads": (AXIS_MODEL,),
    "kv_heads": (AXIS_MODEL,),
    "vocab": (AXIS_MODEL,),
    "experts": (AXIS_MODEL,),       # expert parallelism when E % tp == 0
    "mlp": (AXIS_MODEL,),           # per-expert / dense MLP TP otherwise
    "d_inner": (AXIS_MODEL,),       # mamba inner-dim TP
}


def abstract_mesh(shape: Sequence[int], axes: Sequence[str]):
    """Device-free mesh (tests and dry-runs build shardings on it)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` without replication checking."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --------------------------------------------------------------------------- #
# Rules
# --------------------------------------------------------------------------- #
def rules_for(cfg: ArchConfig, mesh, *, teacher: bool = False) -> dict:
    """Sharding rules for one section of this arch on this mesh.

    teacher=True — forward-only frozen section: drop the FSDP rule
    (``embed`` → data).  A frozen teacher has no optimizer state to
    amortize the per-step all-gather against, so its weights stay
    replicated over the data axis and only TP shards them.

    On a mesh with a non-trivial ``pipe`` axis the stacked ``layers`` dim
    is mapped onto it: parameters and optimizer state live stage-
    partitioned at rest, matching the ``in_specs`` of
    ``repro.dist.pipeline.build_pp_loss``.  When the padded vocab divides
    the pipe axis — the same gate ``build_pp_loss`` uses for its
    vocab-parallel cross-entropy — the ``vocab`` param dim is mapped onto
    ``pipe`` too, so the embed/unembed tables rest exactly where the
    staged loss consumes them (vocab slice per stage)."""
    rules = dict(DEFAULT_RULES)
    if teacher:
        del rules["embed"]
    pp = dict(mesh.shape).get(AXIS_PIPE, 1)
    if pp > 1:
        rules["layers"] = (AXIS_PIPE,)
        if cfg.padded_vocab % pp == 0:
            rules["vocab"] = (AXIS_PIPE,) + DEFAULT_RULES["vocab"]
    return rules


def _candidates(rules: dict, name) -> Tuple[str, ...]:
    cand = rules.get(name, ())
    if cand is None:
        return ()
    if isinstance(cand, str):
        return (cand,)
    return tuple(cand)


def spec_for(spec: ParamSpec, mesh, rules: Optional[dict] = None) -> P:
    """PartitionSpec for one parameter: greedy left-to-right assignment,
    no mesh axis used twice, divisibility fallback → None (replicate)."""
    rules = DEFAULT_RULES if rules is None else rules
    axis_sizes = dict(mesh.shape)
    used: set = set()
    entries = []
    for dim, name in zip(spec.shape, spec.axes):
        entry = None
        for ax in _candidates(rules, name):
            if ax in axis_sizes and ax not in used \
                    and dim % axis_sizes[ax] == 0:
                entry = ax
                used.add(ax)
                break
        entries.append(entry)
    return P(*entries)


def zero_extend(spec: ParamSpec, base: P, mesh) -> P:
    """Extend a parameter's spec over free mesh axes (ZeRO §: optimizer
    state sharded where the weight is replicated).  The stacked ``layers``
    dim is never extended (it is the scan dim)."""
    axis_sizes = dict(mesh.shape)
    entries = [base[i] if i < len(base) else None
               for i in range(len(spec.shape))]
    used = {a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    for ax in mesh.axis_names:
        if ax in used or ax not in ZERO_AXES:
            continue
        n = axis_sizes[ax]
        for i, (dim, name) in enumerate(zip(spec.shape, spec.axes)):
            if name == "layers":
                continue
            cur = entries[i]
            cur_t = () if cur is None else (
                cur if isinstance(cur, tuple) else (cur,))
            prod = n
            for a in cur_t:
                prod *= axis_sizes[a]
            if dim % prod == 0:
                entries[i] = cur_t + (ax,)
                used.add(ax)
                break
    return P(*entries)


# --------------------------------------------------------------------------- #
# Sharding trees
# --------------------------------------------------------------------------- #
def param_shardings(specs, mesh, rules: Optional[dict] = None):
    """NamedSharding tree for a ParamSpec tree."""
    return tree_map_specs(
        lambda s: NamedSharding(mesh, spec_for(s, mesh, rules)), specs)


def opt_state_shardings(specs, mesh, rules: Optional[dict] = None, *,
                        zero: bool = True):
    """AdamWState-shaped sharding tree: ``mu``/``nu``/``master`` get the
    parameter's spec, extended over free mesh axes when ``zero``."""
    from repro.optim.adamw import AdamWState

    def one(s: ParamSpec):
        base = spec_for(s, mesh, rules)
        if zero:
            base = zero_extend(s, base, mesh)
        return NamedSharding(mesh, base)

    tree = tree_map_specs(one, specs)
    # NamedSharding leaves are immutable: the three slots share one tree
    return AdamWState(step=replicated(mesh), mu=tree, nu=tree, master=tree)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# --------------------------------------------------------------------------- #
# Data-parallel helpers
# --------------------------------------------------------------------------- #
def dp_axes(mesh) -> Tuple[str, ...]:
    """The mesh axes carrying data parallelism, outermost first."""
    return tuple(a for a in mesh.axis_names if a in DP_AXES)


def axis_size(mesh, axes) -> int:
    """Product of mesh-axis sizes; axes may be a name, a tuple, or None."""
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    sizes = dict(mesh.shape)
    n = 1
    for a in axes:
        n *= sizes[a]
    return n


def batch_spec(mesh, batch: int, seq_len: int) -> P:
    """[B, S] activation spec: shard batch over the dp axes; B=1 long-decode
    fallback shards the sequence instead; replicate when neither divides."""
    dp = dp_axes(mesh)
    n = axis_size(mesh, dp)
    if not dp:
        return P(None, None)
    if batch % n == 0:
        return P(dp, None)
    if seq_len % n == 0:
        return P(None, dp)
    return P(None, None)


def dp_sharding(mesh, ndim: int = 2) -> NamedSharding:
    """Activation sharding with dim 0 (batch) over the dp axes and every
    other dim replicated — the cross-section handoff layout."""
    dp = dp_axes(mesh)
    return NamedSharding(
        mesh, P(dp if dp else None, *([None] * (ndim - 1))))


def logits_sharding(mesh, batch: int, vocab: int) -> NamedSharding:
    """[B, V] logits: batch over dp, vocab over model (divisibility
    fallback → replicate per dim)."""
    dp = dp_axes(mesh)
    b_ax = dp if dp and batch % axis_size(mesh, dp) == 0 else None
    m = dict(mesh.shape).get(AXIS_MODEL, 1)
    v_ax = AXIS_MODEL if AXIS_MODEL in mesh.axis_names \
        and vocab % m == 0 else None
    return NamedSharding(mesh, P(b_ax, v_ax))


def data_shardings(mesh, batch_specs) -> dict:
    """NamedSharding tree for a batch of ShapeDtypeStructs: dim 0 (batch)
    over the dp axes when divisible, else dim 1 (sequence), else replicated.
    On a CP mesh (``seq`` axis > 1) dim 1 is additionally sequence-sharded
    over ``seq`` when divisible, matching the activation layout
    ``cp_attention`` expects."""
    dp = dp_axes(mesh)
    n = axis_size(mesh, dp)
    cp = dict(mesh.shape).get(AXIS_SEQ, 1)

    def one(leaf):
        entries = [None] * leaf.ndim
        if dp and leaf.ndim >= 1 and leaf.shape[0] % n == 0:
            entries[0] = dp
        elif dp and leaf.ndim >= 2 and leaf.shape[1] % n == 0:
            entries[1] = dp
        if cp > 1 and leaf.ndim >= 2 and entries[1] is None \
                and leaf.shape[1] % cp == 0:
            entries[1] = AXIS_SEQ
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map(one, batch_specs)


# --------------------------------------------------------------------------- #
# Decode-cache shardings
# --------------------------------------------------------------------------- #
def kv_cache_spec(mesh, shape: Tuple[int, ...], kind: str = "attn") -> P:
    """Spec for one [B, C, KV, hd] KV-cache buffer.  KV heads shard over
    ``model`` when divisible; a kv=1 (MQA) cache shards the *sequence* over
    ``model`` instead (flash-decoding split)."""
    B, C, KV, _ = shape
    dp = dp_axes(mesh)
    b_ax = dp if dp and B % axis_size(mesh, dp) == 0 else None
    m = dict(mesh.shape).get(AXIS_MODEL, 1)
    if AXIS_MODEL in mesh.axis_names and KV % m == 0:
        return P(b_ax, None, AXIS_MODEL, None)
    if AXIS_MODEL in mesh.axis_names and C % m == 0:
        return P(b_ax, AXIS_MODEL, None, None)
    return P(b_ax, None, None, None)


def _ssm_cache_spec(mesh, leaf, key: str) -> P:
    """Mamba cache leaves: ``conv`` [B, W, ch] / ``ssm`` [B, nh, hd, n]
    (possibly layer-stacked).  Batch over dp; channels/heads over model."""
    lead = leaf.ndim - (3 if key == "conv" else 4)
    shape = leaf.shape[lead:]
    dp = dp_axes(mesh)
    b_ax = dp if dp and shape[0] % axis_size(mesh, dp) == 0 else None
    m = dict(mesh.shape).get(AXIS_MODEL, 1)
    has_m = AXIS_MODEL in mesh.axis_names
    if key == "conv":
        ch_ax = AXIS_MODEL if has_m and shape[2] % m == 0 else None
        tail = (b_ax, None, ch_ax)
    else:
        h_ax = AXIS_MODEL if has_m and shape[1] % m == 0 else None
        tail = (b_ax, h_ax, None, None)
    return P(*((None,) * lead + tail))


def cache_shardings(mesh, cache_specs):
    """NamedSharding tree for a decode-cache ShapeDtypeStruct tree.  Leaf
    kind is taken from its key ('k'/'v' → attention, 'conv'/'ssm' → mamba);
    leading layer-stack dims are replicated."""
    def one(path, leaf):
        key = None
        for entry in reversed(path):
            k = getattr(entry, "key", None)
            if isinstance(k, str):
                key = k
                break
        if key in ("conv", "ssm"):
            spec = _ssm_cache_spec(mesh, leaf, key)
        else:
            lead = leaf.ndim - 4
            spec = P(*((None,) * lead
                       + tuple(kv_cache_spec(mesh, leaf.shape[lead:]))))
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, cache_specs)


# --------------------------------------------------------------------------- #
# Physical-layout helpers
# --------------------------------------------------------------------------- #
def head_pad_for(cfg: ArchConfig, tp: int) -> int:
    """Zero Q-heads to append so (H + pad) divides the TP axis while
    preserving whole KV groups ((H + pad) % KV == 0).  0 when no attention
    or already divisible."""
    H, KV = cfg.num_heads, cfg.num_kv_heads
    if H == 0 or tp <= 1 or H % tp == 0:
        return 0
    Hp = H + 1
    while Hp % tp or (KV and Hp % KV):
        Hp += 1
    return Hp - H


def section_mesh(devices: Sequence, parallel: ParallelConfig,
                 name: str = "") -> Mesh:
    """Physical mesh for one section: ``ParallelConfig(dp, tp, pp, cp)``
    maps 1:1 onto ``(data, pipe, seq, model)`` axes (sizes may be 1)."""
    n = parallel.devices
    assert len(devices) == n, (name, len(devices), n)
    group = np.array(list(devices)).reshape(
        parallel.dp, parallel.pp, parallel.cp, parallel.tp)
    return Mesh(group, (AXIS_DATA, AXIS_PIPE, AXIS_SEQ, AXIS_MODEL))
