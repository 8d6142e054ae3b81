"""Declarative section-centric workload API (paper §3 end to end).

Maestro's core claim is that a compound workload *is* its section graph:
each section carries its own parallelism ``C^s``, execution mode and
data-dependent activation, and everything else — carved meshes, per-section
jitted steps, jitted AdamW with a shared joint grad-norm, wavefront-ordered
dispatch, the realized timeline — is generic machinery.  This module makes
that the API:

* :class:`SectionSpec` — one section: arch + params + a plain
  ``fn(params, inputs) -> {port: array}`` (producer) or ``-> loss``
  (the critical loss section), a :class:`ParallelConfig`, mode
  (``fwd_bwd`` / ``fwd_only``), an optional per-sample activation
  predicate, and typed emit/consume ports.
* :class:`WorkloadSpec` — the sections plus the cross-section edges
  (implied by ``consumes``); validated at spec-compile time (port-type
  mismatches, cycles, cotangent routing) before any device work.
* :class:`CompoundRuntime` — ONE generic runtime that compiles any
  ``WorkloadSpec`` into the disaggregated execution the bespoke
  ``DistillRuntime`` / ``MLLMRuntime`` classes used to hand-write; both
  are now thin declarations on this API (see ``repro.distill.workload``
  and ``repro.mllm.workload``), as is multi-teacher distillation
  (``repro.distill.multi_teacher``).

Execution model (exactly the structure the MLLM runtime is proven
bit-for-bit equivalent to its colocated oracle with):

* per microbatch, producer sections run ``fwd`` tasks on their carved
  meshes (emitted ports pushed through the MessageQueue), the critical
  section computes loss + grads w.r.t. its params and any consumed ports
  from trainable producers (cotangents pushed back), trainable producers
  run ``bwd`` (vjp) tasks;
* gradients accumulate into f32 zero-seeded trees in microbatch dispatch
  order, are normalized by ``n_mb`` once, and per-section *jitted* AdamW
  updates share one joint grad-norm across all trainable sections (the
  colocated clipping semantics — ``adamw.update(gnorm=)``);
* a section with ``ParallelConfig.grad_compress`` ∈ {"bf16", "int8"}
  defers its DP gradient all-reduce to the ``upd`` dispatch and runs it
  compressed (``repro.optim.compression``): its grad/bwd jits move into
  a shard_map over the data axis and emit stacked per-shard partial
  grads ``[dp, ...]`` (the local loss carries a 1/dp scale so partials
  sum to the DP mean and port cotangents keep colocated scale), and the
  int8 error-feedback residual threads across iterations per section
  (zero-init at first ``install()``, preserved after);
* each trainable section's grad-finalize + AdamW update runs as an
  ``upd`` Dispatch on *that section's own worker* (not the main thread):
  the joint grad-norm is a small cross-worker rendezvous of per-leaf
  sum-of-squares vectors through the MessageQueue, and per-section
  worker FIFO serializes ``upd(i)`` before that section's ``fwd(i+1)``
  with no global barrier between iterations;
* iterations stream: ``install()`` adopts params/opts as runtime state,
  ``submit_iteration()`` enqueues one global batch onto the section
  streams (traffic scoped under a monotonic ``s<i>/`` namespace, evicted
  at retirement), ``retire()`` drains the oldest; the ``lookahead`` knob
  bounds how many iterations may be in flight (0 ⇒ fully serialized,
  today's semantics); ``train_iteration()`` is the serialized
  compatibility wrapper;
* a section with an activation predicate simply emits no Dispatch for a
  microbatch none of whose samples activate it, and its consumers
  substitute the port's exact-zero fill;
* every jit is traced + compiled from the main thread (the act-hook /
  attention-impl globals are not thread-safe at trace time), and every
  task blocks its section-mesh arrays before returning (XLA CPU deadlocks
  when two host threads interleave collective launches on one device set
  — moving the updates onto the section workers means every
  collective-bearing program a mesh runs is launched by its one worker).
"""
from __future__ import annotations

import collections
import contextlib
import functools
import logging
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import cost_model as cmdl
from repro.core.executor import Dispatch, mark_start, order_samples
from repro.core.graph import SectionGraph
from repro.core.runtime import MaestroRuntime
from repro.core.scheduler import ScheduleResult
from repro.core.types import ArchConfig, ParallelConfig, SectionConfig
from repro.dist import sharding as shd
from repro.models import attention as att
from repro.models import common as cm
from repro.optim import adamw, schedules
from repro.optim import compression as gcomp

#: symbolic sequence-length dim in Field / Port shapes, resolved to the
#: workload's seq_len at build time (static dims stay ints)
SEQ = "S"

_log = logging.getLogger("repro.workload")


def _spec_has_axis(spec, axis: str) -> bool:
    """Whether a PartitionSpec mentions ``axis`` in any dim entry."""
    for entry in spec:
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        if axis in entries:
            return True
    return False


def _np_dtype(dt):
    if isinstance(dt, str):
        return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
                "float16": jnp.float16, "int32": jnp.int32}[dt]
    return dt


def _resolve_shape(shape: Tuple, seq_len: Optional[int]) -> Tuple[int, ...]:
    out = []
    for d in shape:
        if d == SEQ:
            assert seq_len is not None, \
                "symbolic 'S' dim used but no seq_len bound yet"
            out.append(int(seq_len))
        else:
            out.append(int(d))
    return tuple(out)


@dataclass(frozen=True)
class Field:
    """One per-sample batch input of a section: shape WITHOUT the batch
    dim (entries int or the symbol :data:`SEQ`).  ``fill`` seeds warmup
    arrays (loss masks warm up as ones so means stay finite)."""
    shape: Tuple
    dtype: Any = "float32"
    fill: float = 0.0


@dataclass(frozen=True)
class Port:
    """A typed cross-section tensor: per-sample shape + dtype.  The same
    ``Port`` object (or an equal one) must appear in the producer's
    ``emits`` and the consumer's ``consumes`` — a mismatch raises at
    spec-compile time, not at trace time."""
    name: str
    shape: Tuple
    dtype: Any = "float32"


@dataclass(frozen=True)
class Consume:
    """Consumer-side declaration of a cross-section edge: the producing
    section plus the *expected* :class:`Port` type."""
    section: str
    port: Port

    @property
    def key(self) -> str:
        return f"{self.section}.{self.port.name}"


@dataclass(frozen=True)
class SectionSpec:
    """One section of a compound workload.

    ``fn(params, inputs) -> {port_name: array}`` for producers, or a
    scalar loss (``loss=True``; ``(loss, aux_scalars)`` with
    ``loss_aux=True``) for the critical section.  ``inputs`` holds, per
    microbatch of capacity ``mbs``:

    * every declared batch :class:`Field` ``[mbs, ...]`` — gathered to
      the activated samples (zero-padded capacity) when the section has
      an ``activation`` predicate, sliced contiguously otherwise;
    * ``"act_valid"`` ``[mbs]`` f32 when the section has a predicate;
    * each consumed port under ``"<section>.<port>"``, plus
      ``"<section>.act_idx"`` / ``"<section>.act_valid"`` when that
      producer has a predicate (for scattering capacity rows back to
      sample slots);
    * every declared const under its name.
    """
    name: str
    arch: ArchConfig
    parallel: ParallelConfig
    fn: Callable[..., Any]
    params: Any                               # tree of ParamSpec
    inputs: Mapping[str, Field] = field(default_factory=dict)
    emits: Tuple[Port, ...] = ()
    consumes: Tuple[Consume, ...] = ()
    mode: str = "fwd_bwd"                     # "fwd_bwd" | "fwd_only"
    loss: bool = False
    loss_aux: bool = False
    activation: Optional[Callable[[Dict[str, np.ndarray]], np.ndarray]] = None
    critical: bool = False
    seq_len: Optional[int] = None             # section sequence length
    consts: Mapping[str, Field] = field(default_factory=dict)

    @property
    def trainable(self) -> bool:
        return self.mode == "fwd_bwd"


@dataclass(frozen=True)
class WorkloadSpec:
    """A compound workload: sections + the edges implied by their
    ``consumes``.  ``global_batch`` / ``seq_len`` / ``mbs`` may be left
    ``None`` for shape-polymorphic workloads — the runtime then binds
    them from the first batch (``mbs=None`` ⇒ one microbatch per
    iteration)."""
    name: str
    sections: Tuple[SectionSpec, ...]
    seq_len: Optional[int] = None
    global_batch: Optional[int] = None
    mbs: Optional[int] = None

    def section(self, name: str) -> SectionSpec:
        for s in self.sections:
            if s.name == name:
                return s
        raise KeyError(name)

    @property
    def critical(self) -> SectionSpec:
        crits = [s for s in self.sections if s.critical]
        assert len(crits) == 1
        return crits[0]

    # ------------------------------------------------------------------ #
    def consumers_of(self, section: str, port: str) -> List[str]:
        return [s.name for s in self.sections
                if any(c.section == section and c.port.name == port
                       for c in s.consumes)]

    def topo_order(self) -> List[str]:
        """Section names, producers before consumers (Kahn)."""
        indeg = {s.name: 0 for s in self.sections}
        for s in self.sections:
            indeg[s.name] = len(s.consumes)
        order, ready = [], [n for n, d in sorted(indeg.items())
                            if d == 0]
        while ready:
            n = ready.pop(0)
            order.append(n)
            for s in self.sections:
                if any(c.section == n for c in s.consumes):
                    indeg[s.name] -= sum(
                        1 for c in s.consumes if c.section == n)
                    if indeg[s.name] == 0:
                        ready.append(s.name)
        if len(order) != len(self.sections):
            raise ValueError(
                f"workload {self.name!r}: section graph has a cycle "
                f"(resolved {order} of {[s.name for s in self.sections]})")
        return order

    # ------------------------------------------------------------------ #
    def validate(self) -> None:
        """Spec-compile-time checks: raise here, before any mesh is
        carved or jit traced."""
        names = [s.name for s in self.sections]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate section names: {names}")
        crits = [s for s in self.sections if s.critical]
        if len(crits) != 1:
            raise ValueError(
                f"workload {self.name!r}: exactly one critical section "
                f"required, got {[s.name for s in crits]}")
        crit = crits[0]
        if not crit.loss or crit.mode != "fwd_bwd":
            raise ValueError(
                f"critical section {crit.name!r} must be a fwd_bwd loss "
                "section (loss=True)")
        if crit.activation is not None:
            raise ValueError(
                f"critical section {crit.name!r} cannot carry an "
                "activation predicate: the loss runs on every microbatch")
        by_name = {s.name: s for s in self.sections}
        for s in self.sections:
            if s.mode not in ("fwd_bwd", "fwd_only"):
                raise ValueError(
                    f"section {s.name!r}: unknown mode {s.mode!r}")
            if s.loss and s.mode == "fwd_only":
                raise ValueError(
                    f"section {s.name!r}: a loss section cannot be "
                    "fwd_only")
            if s.loss and not s.critical:
                raise ValueError(
                    f"section {s.name!r}: loss sections must be the "
                    "critical section")
            if not s.loss and not s.emits:
                raise ValueError(
                    f"section {s.name!r}: a non-loss section must emit "
                    "at least one port")
            pnames = [p.name for p in s.emits]
            if len(set(pnames)) != len(pnames):
                raise ValueError(
                    f"section {s.name!r}: duplicate emitted port names "
                    f"{pnames}")
            for c in s.consumes:
                if c.section == s.name:
                    raise ValueError(
                        f"section {s.name!r} consumes its own port "
                        f"{c.port.name!r}")
                src = by_name.get(c.section)
                if src is None:
                    raise ValueError(
                        f"section {s.name!r} consumes from unknown "
                        f"section {c.section!r}")
                emitted = {p.name: p for p in src.emits}
                if c.port.name not in emitted:
                    raise ValueError(
                        f"section {s.name!r} consumes port "
                        f"{c.port.name!r} which {c.section!r} does not "
                        f"emit (emits {sorted(emitted)})")
                got = emitted[c.port.name]
                if (tuple(got.shape) != tuple(c.port.shape)
                        or _np_dtype(got.dtype) != _np_dtype(c.port.dtype)):
                    raise ValueError(
                        f"port type mismatch on edge {c.section!r} -> "
                        f"{s.name!r}: producer emits "
                        f"{c.port.name!r}{tuple(got.shape)}:{got.dtype} "
                        f"but consumer expects "
                        f"{tuple(c.port.shape)}:{c.port.dtype}")
        # cotangent routing: a trainable producer's port must have exactly
        # one consumer (the bwd task pulls ONE cotangent per port), and
        # that consumer must itself be fwd_bwd — a fwd_only consumer
        # never pushes a cotangent back, so the producer's bwd task would
        # deadlock waiting on it
        for s in self.sections:
            if not s.trainable or s.critical:
                continue
            for p in s.emits:
                cons = self.consumers_of(s.name, p.name)
                if len(cons) != 1:
                    raise ValueError(
                        f"trainable section {s.name!r} port {p.name!r} "
                        f"must have exactly one consumer (cotangent "
                        f"routing), got {cons}")
                if by_name[cons[0]].mode != "fwd_bwd":
                    raise ValueError(
                        f"trainable section {s.name!r} port {p.name!r} "
                        f"is consumed by fwd_only section {cons[0]!r}, "
                        "which can never return a cotangent — the "
                        "producer's bwd task would deadlock; make the "
                        "consumer fwd_bwd or freeze the producer")
        if (self.global_batch is not None and self.mbs is not None
                and self.global_batch % self.mbs):
            raise ValueError(
                f"global_batch={self.global_batch} is not a multiple of "
                f"mbs={self.mbs}")
        self.topo_order()
        # dispatch-graph deadlock proof (repro.analysis.deadlock): the
        # blocking-pull order submit_iteration will emit — incl. the
        # grad-norm rendezvous and lookahead cross-iteration FIFO
        # coupling — must be acyclic with every pull satisfiable.
        # Reject the spec here instead of hanging in drain().
        from repro.analysis import deadlock as _deadlock
        _deadlock.check_spec(self, n_mb=2, lookahead=1).raise_on_error(
            ValueError,
            f"workload {self.name!r}: dispatch-graph deadlock analysis "
            "failed")

    # ------------------------------------------------------------------ #
    def to_graph(self) -> SectionGraph:
        """The cost-model / carving view of this workload (the axis-naming
        and seq_scale contract the scheduler 6-tuples are built from)."""
        g = SectionGraph()
        base_seq = self.seq_len
        for s in self.sections:
            scale = 1.0
            if s.seq_len is not None and base_seq:
                scale = s.seq_len / max(base_seq, 1)
            g.add(SectionConfig(s.name, s.arch, s.parallel,
                                trainable=s.trainable, critical=s.critical,
                                seq_scale=scale))
        for s in self.sections:
            for c in s.consumes:
                port = c.port
                width = int(port.shape[-1]) if port.shape and \
                    port.shape[-1] != SEQ else 1
                bpt = width * jnp.dtype(_np_dtype(port.dtype)).itemsize
                src_dp = self.section(c.section).parallel.dp
                fanout = (s.parallel.dp // src_dp
                          if src_dp and s.parallel.dp % src_dp == 0 else 1)
                g.connect(c.section, s.name, bytes_per_token=bpt,
                          fanout=fanout)
        g.validate()
        return g


# --------------------------------------------------------------------------- #
# Consolidated per-section parallelism validation (replaces the scattered
# _reject_pp / _reject_pp_cp helpers the bespoke runtimes carried)
# --------------------------------------------------------------------------- #
def validate_section_parallel(name: str, arch: ArchConfig,
                              parallel: ParallelConfig, mesh) -> str:
    """ONE validation path for a section's ``C^s`` against its carved
    mesh: routes through ``repro.train.step.parallel_regime`` (the same
    dispatch ``build_train_step`` uses), checks arch-family CP/PP
    support, and rejects PP for declarative workload sections — every
    error names the section and the offending mesh axis."""
    from repro.train.step import _check_pp_cp_support, parallel_regime
    try:
        regime = parallel_regime(mesh, parallel)
    except (ValueError, NotImplementedError) as e:
        raise type(e)(f"section {name!r}: {e}") from None
    if regime == "pp":
        raise NotImplementedError(
            f"section {name!r}: pipeline parallelism (mesh axis "
            f"{shd.AXIS_PIPE!r}={parallel.pp}) is not supported for "
            "declarative workload sections — a section fn cannot be "
            "stage-partitioned by build_pp_loss; use dp/tp/cp for this "
            "section (ROADMAP open item)")
    try:
        _check_pp_cp_support(arch, regime)
    except NotImplementedError as e:
        raise NotImplementedError(f"section {name!r}: {e}") from None
    if regime == "cp":
        cp = dict(mesh.shape).get(shd.AXIS_SEQ, 1)
        # the section's own sequence must divide its seq axis; checked
        # again at build time once seq_len is bound
        if parallel.cp != cp:          # pragma: no cover (regime checked)
            raise ValueError(name)
    return regime


# --------------------------------------------------------------------------- #
# Iteration plan: wavefront order + per-section data-dependent activation
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class SectionActivation:
    """Which microbatches activate a section, and per-microbatch local
    indices/validity of the activating samples (capacity layout)."""
    active_mbs: Tuple[int, ...]
    idx: np.ndarray                   # [n_mb, mbs] int32 local indices
    valid: np.ndarray                 # [n_mb, mbs] f32, 1.0 = real sample


def build_activation(order: Sequence[int], flags: np.ndarray,
                     mbs: int) -> SectionActivation:
    """Per-microbatch activation layout of one section given the sample
    dispatch ``order`` and per-sample ``flags`` (original indexing)."""
    n = len(order)
    assert n % mbs == 0, (n, mbs)
    n_mb = n // mbs
    ordered = np.asarray(flags).astype(bool)[list(order)]
    idx = np.zeros((n_mb, mbs), np.int32)
    valid = np.zeros((n_mb, mbs), np.float32)
    active: List[int] = []
    for i in range(n_mb):
        loc = np.where(ordered[i * mbs:(i + 1) * mbs])[0]
        idx[i, :len(loc)] = loc
        valid[i, :len(loc)] = 1.0
        if len(loc):
            active.append(i)
    return SectionActivation(tuple(active), idx, valid)


@dataclass(frozen=True)
class IterationPlan:
    """Host-side dispatch plan for one global batch."""
    order: Tuple[int, ...]
    mbs: int
    n_mb: int
    activation: Dict[str, SectionActivation]
    schedule: Optional[ScheduleResult] = None

    def section(self, name: str) -> Optional[SectionActivation]:
        return self.activation.get(name)


# --------------------------------------------------------------------------- #
# The one generic compound runtime
# --------------------------------------------------------------------------- #
class _Inflight:
    """Host-side record of one submitted-not-yet-retired iteration."""

    __slots__ = ("seq", "scope", "step_idx", "plan", "return_grads",
                 "ctx", "acc", "crit_acc")

    def __init__(self, seq: int, scope: str, step_idx, plan: IterationPlan,
                 return_grads: bool, trainable: Sequence[str]):
        self.seq = seq
        self.scope = scope
        self.step_idx = step_idx
        self.plan = plan
        self.return_grads = return_grads
        self.ctx: Dict[Tuple[str, int], Any] = {}
        self.acc = {n: {"g": None} for n in trainable}
        self.crit_acc = {"loss": jnp.float32(0.0), "aux": None}


class CompoundRuntime:
    """Compile a :class:`WorkloadSpec` into disaggregated execution on the
    compound executor.  See the module docstring for the execution model;
    ``DistillRuntime`` / ``MLLMRuntime`` / multi-teacher distillation are
    all thin declarations over this class."""

    def __init__(self, spec: WorkloadSpec, *, devices=None,
                 impl: str = "auto", lr_schedule=None,
                 opt_cfg: adamw.AdamWConfig = adamw.AdamWConfig(),
                 lookahead: int = 0):
        spec.validate()
        self.spec = spec
        self.impl = impl
        self.opt_cfg = opt_cfg
        self.lr_fn = lr_schedule or functools.partial(schedules.constant,
                                                      peak_lr=1e-3)
        self.graph = spec.to_graph()
        self.rt = MaestroRuntime(self.graph, devices)
        # mesh-thread affinity (repro.analysis.affinity): disjoint
        # section meshes, one live worker each — the wiring invariant
        # the XLA-CPU collective-launch contract rests on
        from repro.analysis import affinity as _affinity
        _affinity.check_wiring(self.rt).raise_on_error(
            RuntimeError,
            f"workload {spec.name!r}: mesh-thread affinity check failed")
        self.executor = self.rt.executor()
        self.last_execution = None
        #: cross-iteration pipelining depth: how many iterations beyond
        #: the oldest may be in flight at once.  0 ⇒ submit_iteration
        #: retires the previous iteration before dispatching the next
        #: (exactly the old barrier semantics); 1 ⇒ iteration i+1's fwd
        #: tasks stream in behind each section's own upd(i).
        self.lookahead = int(lookahead)
        self._session = self.executor.session()
        self._it_seq = 0
        self._inflight: "collections.deque[_Inflight]" = collections.deque()
        self._retired: "collections.deque[dict]" = collections.deque()
        self._params: Dict[str, Any] = {}
        self._opts: Dict[str, Any] = {}
        #: per-compressed-section stacked [dp, ...] error-feedback
        #: residual — zero-init at first install(), then threaded across
        #: iterations by the section's own ``upd`` dispatch
        self._ef: Dict[str, Any] = {}
        self._installed = False
        self._topo = spec.topo_order()
        self._crit = spec.critical.name
        self._trainable = [s.name for s in spec.sections if s.trainable]
        self._has_activation = any(s.activation is not None
                                   for s in spec.sections)
        # consolidated C^s validation against the carved meshes (this is
        # what lifts the old blanket pp/cp rejections: cp sections route
        # through the same parallel_regime dispatch as build_train_step)
        self._regime: Dict[str, str] = {}
        for s in spec.sections:
            self._regime[s.name] = validate_section_parallel(
                s.name, s.arch, self.rt.parallel(s.name),
                self.rt.mesh(s.name))
        # per-section DP grad-compression knob (ParallelConfig.grad_compress
        # → repro.optim.compression): validated here, realized as stacked
        # per-shard partial grads in the section's grad/bwd jits plus ONE
        # compressed all-reduce in its worker-side ``upd`` dispatch
        self._compress: Dict[str, str] = {}
        self._comp_dp: Dict[str, int] = {}
        for s in spec.sections:
            method = self.rt.parallel(s.name).grad_compress or "none"
            if method == "none":
                continue
            if method not in gcomp.METHODS:
                raise ValueError(
                    f"section {s.name!r}: grad_compress={method!r} — "
                    f"expected one of {gcomp.METHODS}")
            if not s.trainable:
                raise ValueError(
                    f"section {s.name!r}: grad_compress on a fwd_only "
                    "section — frozen sections produce no gradients")
            mesh = self.rt.mesh(s.name)
            sizes = dict(mesh.shape)
            if self._regime[s.name] != "plain" or any(
                    sizes.get(a, 1) > 1
                    for a in (shd.AXIS_PIPE, shd.AXIS_SEQ, shd.AXIS_MODEL)):
                raise NotImplementedError(
                    f"section {s.name!r}: grad_compress requires the plain "
                    "regime on a dp-only mesh — the compressed all-reduce "
                    "runs in a shard_map over the data axis and cannot "
                    "nest inside cp schedules or compose with tp "
                    "activation sharding")
            das = shd.dp_axes(mesh)
            if len(das) != 1:
                raise NotImplementedError(
                    f"section {s.name!r}: grad_compress needs exactly one "
                    f"data axis on the section mesh (got {das!r})")
            by = {x.name: x for x in spec.sections}
            for c in s.consumes:
                if by[c.section].activation is not None:
                    raise NotImplementedError(
                        f"section {s.name!r}: grad_compress on a consumer "
                        f"of activation-predicated section {c.section!r} "
                        "— the capacity-row → sample-slot scatter crosses "
                        "the batch dim the compressed shard_map shards")
            self._compress[s.name] = method
            self._comp_dp[s.name] = sizes[das[0]]
        # shape-independent state: param/opt shardings, update/ssq jits
        self._p_shard: Dict[str, Any] = {}
        self._o_shard: Dict[str, Any] = {}
        self._update: Dict[str, Any] = {}
        self._ssq: Dict[str, Any] = {}
        self._compress_step: Dict[str, Any] = {}
        for s in spec.sections:
            mesh = self.rt.mesh(s.name)
            rules = shd.rules_for(s.arch, mesh, teacher=not s.trainable)
            self._p_shard[s.name] = shd.param_shardings(s.params, mesh,
                                                        rules)
            if not s.trainable:
                continue
            self._o_shard[s.name] = shd.opt_state_shardings(s.params, mesh,
                                                            rules)
            rep = shd.replicated(mesh)
            p_sh, o_sh = self._p_shard[s.name], self._o_shard[s.name]
            # jitted per-section AdamW: the same fused elementwise program
            # a colocated step runs (eager op-by-op updates round
            # differently — no FMA fusion).  gnorm= is only legal with
            # clipping enabled (adamw raises otherwise): without a clip
            # threshold the joint norm is metrics-only.
            if opt_cfg.clip_norm > 0:
                upd = functools.partial(adamw.update, cfg=opt_cfg)

                def upd_fn(g, st, lr, gn, _u=upd):
                    return _u(g, st, lr, gnorm=gn)
                self._update[s.name] = jax.jit(
                    upd_fn, in_shardings=(p_sh, o_sh, rep, rep),
                    out_shardings=(p_sh, o_sh, rep),
                    donate_argnums=(1,))
            else:
                def upd_fn(g, st, lr, _cfg=opt_cfg):
                    return adamw.update(g, st, lr, _cfg)
                self._update[s.name] = jax.jit(
                    upd_fn, in_shardings=(p_sh, o_sh, rep),
                    out_shardings=(p_sh, o_sh, rep),
                    donate_argnums=(1,))

            def ssq_vec(g):
                return jnp.stack([jnp.sum(jnp.square(x.astype(jnp.float32)))
                                  for x in jax.tree_util.tree_leaves(g)])
            # jitted per-leaf sums of squares: the same compiled
            # square+sum subgraph an in-jit global_norm runs
            self._ssq[s.name] = jax.jit(ssq_vec, in_shardings=(p_sh,),
                                        out_shardings=rep)
            if s.name in self._compress:
                self._compress_step[s.name] = self._make_compress_step(
                    s.name, self._compress[s.name], p_sh)
        self._built: Optional[Tuple[int, int, int]] = None
        if spec.global_batch is not None and spec.seq_len is not None:
            self._build(spec.global_batch, spec.seq_len,
                        spec.mbs or spec.global_batch)

    # ------------------------------------------------------------------ #
    # params / optimizer state
    # ------------------------------------------------------------------ #
    def init(self, rng):
        """Init + place every section's params (spec order rng split) and
        matching optimizer states for the trainable sections."""
        rngs = jax.random.split(rng, len(self.spec.sections))
        drawn = {s.name: cm.init_params(s.params, r, self._p_shard[s.name])
                 for s, r in zip(self.spec.sections, rngs)}
        return self.place(drawn)

    def place(self, params: Dict[str, Any]):
        """Place per-section param trees onto the carved meshes and build
        matching optimizer states."""
        placed = {n: jax.device_put(params[n], self._p_shard[n])
                  for n in params}
        opts = {n: jax.device_put(adamw.init(placed[n]), self._o_shard[n])
                for n in self._trainable}
        return placed, opts

    # ------------------------------------------------------------------ #
    # shape binding: jits, input/port shardings, warmup
    # ------------------------------------------------------------------ #
    def _ensure_built(self, host: Dict[str, np.ndarray]) -> None:
        B = None
        for s in self.spec.sections:
            for k in s.inputs:
                B = len(host[k])
                break
            if B is not None:
                break
        assert B is not None, "no section declares batch inputs"
        S = self.spec.seq_len
        if S is None:
            for s in self.spec.sections:
                for k, f in s.inputs.items():
                    if SEQ in tuple(f.shape):
                        S = int(host[k].shape[1 + tuple(f.shape).index(SEQ)])
                        break
                if S is not None:
                    break
        mbs = self.spec.mbs or B
        # normalize seq to the stored key (seq-free specs bind S=None but
        # _built records 0) so a None-seq workload doesn't re-jit per step
        if self._built != (B, S or 0, mbs):
            self._build(B, S, mbs)

    def _build(self, global_batch: int, seq_len: Optional[int],
               mbs: int) -> None:
        assert global_batch % mbs == 0, (global_batch, mbs)
        if getattr(self, "_inflight", None):
            raise RuntimeError(
                "cannot rebind workload shapes with iterations in "
                "flight — drain() first")
        self.B, self.S, self.mbs = global_batch, seq_len, mbs
        self.n_mb = global_batch // mbs
        spec = self.spec
        self._in_shard: Dict[str, Dict[str, Any]] = {}
        self._in_spec: Dict[str, Dict[str, Tuple[Tuple[int, ...], Any,
                                                 float]]] = {}
        self._pull_shard: Dict[str, Dict[str, Any]] = {}
        self._ct_pull_shard: Dict[str, Dict[str, Any]] = {}
        self._port_zero: Dict[str, Dict[str, Tuple[Tuple[int, ...],
                                                   Any]]] = {}
        self._fwd: Dict[str, Any] = {}
        self._bwd: Dict[str, Any] = {}
        self._ctx: Dict[str, Any] = {}
        self._grad = None
        self._grad_has_ct = False
        by_name = {s.name: s for s in spec.sections}

        for name in self._topo:
            s = by_name[name]
            mesh = self.rt.mesh(name)
            sec_seq = s.seq_len if s.seq_len is not None else seq_len
            cp = dict(mesh.shape).get(shd.AXIS_SEQ, 1)
            if cp > 1 and (sec_seq is None or sec_seq % cp):
                raise ValueError(
                    f"section {name!r}: sequence length {sec_seq} does "
                    f"not divide the mesh {shd.AXIS_SEQ!r} axis ({cp})")
            if name in self._compress and mbs % self._comp_dp[name]:
                raise NotImplementedError(
                    f"section {name!r}: grad_compress needs the "
                    f"microbatch size ({mbs}) to divide the data axis "
                    f"({self._comp_dp[name]}) so every shard owns a real "
                    "slice of the batch")
            from repro.train.step import _act_hook_for
            hook = _act_hook_for(mesh, mbs, sec_seq or 1)
            if self._regime[name] == "cp":
                from repro.dist import context as cpx
                cp_impl = cpx.cp_attention_impl(
                    mesh, batch_axes=shd.dp_axes(mesh) or None,
                    mode=s.parallel.cp_mode, impl=s.parallel.cp_impl,
                    overlap_chunks=s.parallel.cp_overlap_chunks,
                    section=name)
                ctx = functools.partial(att.attention_impl, cp_impl)
            else:
                ctx = contextlib.nullcontext
            self._ctx[name] = (hook, ctx)

            # ---- input layout: every key the section fn will see ------ #
            in_shard: Dict[str, Any] = {}
            in_spec: Dict[str, Tuple[Tuple[int, ...], Any, float]] = {}
            rep = shd.replicated(mesh)
            for k, f in s.inputs.items():
                shp = (mbs,) + _resolve_shape(tuple(f.shape), sec_seq)
                in_shard[k] = shd.dp_sharding(mesh, len(shp))
                in_spec[k] = (shp, _np_dtype(f.dtype), f.fill)
            if s.activation is not None:
                in_shard["act_valid"] = shd.dp_sharding(mesh, 1)
                in_spec["act_valid"] = ((mbs,), jnp.float32, 0.0)
            for cn, f in s.consts.items():
                shp = _resolve_shape(tuple(f.shape), sec_seq)
                in_shard[cn] = rep
                in_spec[cn] = (shp, _np_dtype(f.dtype), f.fill)
            pull_shard: Dict[str, Any] = {}
            for c in s.consumes:
                shp = (mbs,) + _resolve_shape(tuple(c.port.shape), seq_len)
                pull_shard[c.key] = shd.dp_sharding(mesh, len(shp))
                if by_name[c.section].activation is not None:
                    in_shard[f"{c.section}.act_idx"] = rep
                    in_spec[f"{c.section}.act_idx"] = ((mbs,), jnp.int32,
                                                       0.0)
                    in_shard[f"{c.section}.act_valid"] = rep
                    in_spec[f"{c.section}.act_valid"] = ((mbs,),
                                                         jnp.float32, 0.0)
            self._in_shard[name] = in_shard
            self._in_spec[name] = in_spec
            self._pull_shard[name] = pull_shard
            self._port_zero[name] = {
                c.key: ((mbs,) + _resolve_shape(tuple(c.port.shape),
                                                seq_len),
                        _np_dtype(c.port.dtype))
                for c in s.consumes}
            # cotangent pulls for this section's OWN emitted ports
            # (producer-mesh dp layout)
            self._ct_pull_shard[name] = {
                p.name: shd.dp_sharding(
                    mesh, 1 + len(_resolve_shape(tuple(p.shape), seq_len)))
                for p in s.emits}

        self._build_jits(by_name)
        self._warmup(by_name)
        self._built = (global_batch, seq_len or 0, mbs)

    # consumed keys whose cotangents matter (src is a trainable producer)
    def _ct_keys(self, s: SectionSpec) -> List[str]:
        by_name = {x.name: x for x in self.spec.sections}
        return [c.key for c in s.consumes
                if by_name[c.section].trainable]

    # ------------------------------------------------------------------ #
    # DP grad compression (ParallelConfig.grad_compress): the section's
    # grad/bwd jits move into a shard_map over the data axis and emit
    # STACKED per-shard partial grads [dp, ...] instead of XLA's
    # implicitly all-reduced full grads; the reduce is deferred to the
    # section's ``upd`` dispatch where it runs compressed.
    # ------------------------------------------------------------------ #
    def _make_compress_step(self, name: str, method: str, p_sh):
        """Jitted compressed grad-finalize for one section: stacked
        per-shard f32 partial grads ``[dp, ...]`` plus the stacked
        error-feedback residual → ONE compressed all-reduce over the data
        axis (``repro.optim.compression``) → (param-dtype reduced grads,
        new stacked residual).  Replaces the eager ``(g / n_mb).astype``
        finalize in ``upd_task``; 1/n_mb folds in via ``inv_n``."""
        mesh = self.rt.mesh(name)
        da = shd.dp_axes(mesh)[0]
        rep = shd.replicated(mesh)
        ef_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P(da)), p_sh)
        shapes = cm.param_shapes(self.spec.section(name).params)

        def finalize(g_stacked, ef_stacked, inv_n):
            g = jax.tree_util.tree_map(lambda x: x[0] * inv_n, g_stacked)
            ef = gcomp.ErrorFeedback(jax.tree_util.tree_map(
                lambda x: x[0], ef_stacked))
            # mean=False: the partial grads already carry the 1/dp the
            # loss was scaled by, so the compressed SUM is the DP mean
            red, new_ef = gcomp.ef_compress_tree(g, ef, da, method,
                                                 mean=False)
            red = jax.tree_util.tree_map(
                lambda r, sp: r.astype(sp.dtype), red, shapes)
            return red, jax.tree_util.tree_map(lambda x: x[None],
                                               new_ef.residual)

        run = shd.shard_map(finalize, mesh, (P(da), P(da), P()),
                            (P(), P(da)))
        # donate only the residual: the reduced grads are param-shaped,
        # so the stacked-grad buffer has no donatable consumer (warning)
        return jax.jit(run, in_shardings=(ef_sh, ef_sh, rep),
                       out_shardings=(p_sh, ef_sh),
                       donate_argnums=(1,))

    def _ef_init(self, name: str, params) -> Any:
        """Zero-initialized stacked [dp, ...] error-feedback residual for
        one compressed section, placed on its data axis."""
        mesh = self.rt.mesh(name)
        da = shd.dp_axes(mesh)[0]
        dpn = self._comp_dp[name]
        z = jax.tree_util.tree_map(
            lambda x: jnp.zeros((dpn,) + x.shape, jnp.float32), params)
        return jax.device_put(
            z, jax.tree_util.tree_map(
                lambda _: NamedSharding(mesh, P(da)), z))

    def _sharded_grad_jit(self, s: SectionSpec, p_sh, ct_sh, rest_sh,
                          rep):
        """Compressed critical section: loss + grads run in a shard_map
        over the data axis, each shard seeing only its local batch slice.
        The local loss is scaled 1/dp inside so (a) the stacked partial
        grads sum to the DP-mean gradient and (b) pushed port cotangents
        keep the colocated per-element scale.  The reported loss/aux are
        the psum of the scaled locals — the mean over shards, which for
        sample-decomposable losses matches the colocated global mean
        within fp tolerance (masked means deviate only when the mask is
        unbalanced across shards; documented in docs/perf.md)."""
        name = s.name
        mesh = self.rt.mesh(name)
        da = shd.dp_axes(mesh)[0]
        dp = self._comp_dp[name]
        _fn, _aux = s.fn, s.loss_aux
        p_specs = jax.tree_util.tree_map(lambda sh: sh.spec, p_sh)
        rest_specs = {k: sh.spec for k, sh in rest_sh.items()}
        g_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P(da)), p_sh)

        def scaled(p, inputs):
            # act-hook sharding constraints are illegal inside shard_map
            with cm.act_hook(None):
                val = _fn(p, inputs)
            if _aux:
                return val[0] / dp, val[1]
            return val / dp

        def reduce_val(val):
            if _aux:
                return (jax.lax.psum(val[0], da),
                        jax.tree_util.tree_map(
                            lambda a: jax.lax.psum(a / dp, da), val[1]))
            return jax.lax.psum(val, da)

        def stack32(g):
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32)[None], g)

        if ct_sh is not None:
            ct_specs = {k: sh.spec for k, sh in ct_sh.items()}

            def grad_fn(params, cts, rest):
                def f(p, c):
                    return scaled(p, {**rest, **c})
                val, (g_p, g_c) = jax.value_and_grad(
                    f, argnums=(0, 1), has_aux=_aux)(params, cts)
                # a replicated ct input sees identical local grads that
                # are each a PARTIAL derivative → reduce; batch-sharded
                # cts assemble shard-local slices as-is
                g_c = {k: (v if _spec_has_axis(ct_specs[k], da)
                           else jax.lax.psum(v, da))
                       for k, v in g_c.items()}
                return reduce_val(val), stack32(g_p), g_c

            run = shd.shard_map(grad_fn, mesh,
                                (p_specs, ct_specs, rest_specs),
                                (P(), P(da), ct_specs))
            return jax.jit(run, in_shardings=(p_sh, ct_sh, rest_sh),
                           out_shardings=(rep, g_sh, ct_sh))

        def grad_fn(params, rest):
            def f(p):
                return scaled(p, rest)
            val, g_p = jax.value_and_grad(f, has_aux=_aux)(params)
            return reduce_val(val), stack32(g_p)

        run = shd.shard_map(grad_fn, mesh, (p_specs, rest_specs),
                            (P(), P(da)))
        return jax.jit(run, in_shardings=(p_sh, rest_sh),
                       out_shardings=(rep, g_sh))

    def _sharded_bwd_jit(self, s: SectionSpec, p_sh, ct_sh, rest_sh,
                         all_in_sh, ct_out_sh):
        """Compressed trainable producer: the vjp runs in a shard_map over
        the data axis against the shard-local slice of the pulled
        cotangents (which already carry the colocated global scale), so
        the stacked per-shard partial grads sum to the full gradient."""
        name = s.name
        mesh = self.rt.mesh(name)
        da = shd.dp_axes(mesh)[0]
        _fn = s.fn
        p_specs = jax.tree_util.tree_map(lambda sh: sh.spec, p_sh)
        ct_out_specs = {k: sh.spec for k, sh in ct_out_sh.items()}
        g_sh = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, P(da)), p_sh)

        def call(p, inputs):
            with cm.act_hook(None):
                return _fn(p, inputs)

        def stack32(g):
            return jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float32)[None], g)

        if ct_sh is not None:
            ct_specs = {k: sh.spec for k, sh in ct_sh.items()}
            rest_specs = {k: sh.spec for k, sh in rest_sh.items()}

            def bwd_fn(params, cts_in, rest, cts):
                def f(p, c):
                    return call(p, {**rest, **c})
                _, vjp = jax.vjp(f, params, cts_in)
                g_p, g_c = vjp(cts)
                g_c = {k: (v if _spec_has_axis(ct_specs[k], da)
                           else jax.lax.psum(v, da))
                       for k, v in g_c.items()}
                return stack32(g_p), g_c

            run = shd.shard_map(
                bwd_fn, mesh,
                (p_specs, ct_specs, rest_specs, ct_out_specs),
                (P(da), ct_specs))
            return jax.jit(
                run, in_shardings=(p_sh, ct_sh, rest_sh, ct_out_sh),
                out_shardings=(g_sh, ct_sh))

        all_in_specs = {k: sh.spec for k, sh in all_in_sh.items()}

        def bwd_fn(params, inputs, cts):
            def f(p):
                return call(p, inputs)
            _, vjp = jax.vjp(f, params)
            return stack32(vjp(cts)[0])

        run = shd.shard_map(bwd_fn, mesh,
                            (p_specs, all_in_specs, ct_out_specs), P(da))
        return jax.jit(run, in_shardings=(p_sh, all_in_sh, ct_out_sh),
                       out_shardings=g_sh)

    def _build_jits(self, by_name: Dict[str, SectionSpec]) -> None:
        for name in self._topo:
            s = by_name[name]
            hook, ctx = self._ctx[name]
            p_sh = self._p_shard[name]
            in_sh = self._in_shard[name]
            pull_sh = self._pull_shard[name]
            ct_keys = self._ct_keys(s)

            def call(fn, params, inputs, _hook=hook, _ctx=ctx):
                with cm.act_hook(*_hook), _ctx():
                    return fn(params, inputs)

            if s.critical:
                rep = shd.replicated(self.rt.mesh(name))
                rest_sh = {**in_sh, **{k: v for k, v in pull_sh.items()
                                       if k not in ct_keys}}
                self._grad_has_ct = bool(ct_keys)
                if name in self._compress:
                    ct_sh = ({k: pull_sh[k] for k in ct_keys}
                             if ct_keys else None)
                    self._grad = self._sharded_grad_jit(s, p_sh, ct_sh,
                                                        rest_sh, rep)
                    continue
                if ct_keys:
                    ct_sh = {k: pull_sh[k] for k in ct_keys}

                    def grad_fn(params, cts, rest, _fn=s.fn,
                                _call=call, _aux=s.loss_aux):
                        def f(p, c):
                            return _call(_fn, p, {**rest, **c})
                        val, (g_p, g_c) = jax.value_and_grad(
                            f, argnums=(0, 1), has_aux=_aux)(params, cts)
                        return val, g_p, g_c
                    self._grad = jax.jit(
                        grad_fn,
                        in_shardings=(p_sh, ct_sh, rest_sh),
                        out_shardings=(rep, p_sh, ct_sh))
                else:
                    def grad_fn(params, rest, _fn=s.fn, _call=call,
                                _aux=s.loss_aux):
                        def f(p):
                            return _call(_fn, p, rest)
                        val, g_p = jax.value_and_grad(
                            f, has_aux=_aux)(params)
                        return val, g_p
                    self._grad = jax.jit(
                        grad_fn, in_shardings=(p_sh, rest_sh),
                        out_shardings=(rep, p_sh))
                continue

            # ---- producer fwd ---------------------------------------- #
            all_in_sh = {**in_sh, **pull_sh}

            def fwd_fn(params, inputs, _fn=s.fn, _call=call):
                return _call(_fn, params, inputs)
            self._fwd[name] = jax.jit(fwd_fn,
                                      in_shardings=(p_sh, all_in_sh))

            # ---- producer bwd (vjp; recompute like the bespoke
            # runtimes did — remat is the section fn's business) -------- #
            if not s.trainable:
                continue
            ct_out_sh = self._ct_pull_shard[name]
            if name in self._compress:
                if ct_keys:
                    ct_sh = {k: pull_sh[k] for k in ct_keys}
                    rest_keys_sh = {**in_sh,
                                    **{k: v for k, v in pull_sh.items()
                                       if k not in ct_keys}}
                    self._bwd[name] = self._sharded_bwd_jit(
                        s, p_sh, ct_sh, rest_keys_sh, None, ct_out_sh)
                else:
                    self._bwd[name] = self._sharded_bwd_jit(
                        s, p_sh, None, None, all_in_sh, ct_out_sh)
                continue
            if ct_keys:
                ct_sh = {k: pull_sh[k] for k in ct_keys}
                rest_keys_sh = {**in_sh,
                                **{k: v for k, v in pull_sh.items()
                                   if k not in ct_keys}}

                def bwd_fn(params, cts_in, rest, cts, _fn=s.fn,
                           _call=call):
                    def f(p, c):
                        return _call(_fn, p, {**rest, **c})
                    _, vjp = jax.vjp(f, params, cts_in)
                    g_p, g_c = vjp(cts)
                    return g_p, g_c
                self._bwd[name] = jax.jit(
                    bwd_fn,
                    in_shardings=(p_sh, ct_sh, rest_keys_sh, ct_out_sh),
                    out_shardings=(p_sh, ct_sh))
            else:
                def bwd_fn(params, inputs, cts, _fn=s.fn, _call=call):
                    def f(p):
                        return _call(_fn, p, inputs)
                    _, vjp = jax.vjp(f, params)
                    return vjp(cts)[0]
                self._bwd[name] = jax.jit(
                    bwd_fn, in_shardings=(p_sh, all_in_sh, ct_out_sh),
                    out_shardings=p_sh)

    # ------------------------------------------------------------------ #
    def _zero_inputs(self, name: str) -> Dict[str, Any]:
        out = {}
        for k, (shp, dt, fill) in self._in_spec[name].items():
            if k.endswith(".act_idx"):
                out[k] = jnp.arange(shp[0], dtype=jnp.int32)
            elif fill:
                out[k] = jnp.full(shp, fill, dt)
            else:
                out[k] = jnp.zeros(shp, dt)
        return out

    def _warmup(self, by_name: Dict[str, SectionSpec]) -> None:
        """Trace + compile every worker-thread jit from the main thread:
        the act-hook / attention-impl globals are process-wide, so
        concurrent first-call tracing from two section workers races."""
        params = {s.name: cm.init_params(s.params, jax.random.PRNGKey(i),
                                         self._p_shard[s.name])
                  for i, s in enumerate(self.spec.sections)}
        outs = []
        for name in self._topo:
            s = by_name[name]
            inputs = self._zero_inputs(name)
            for c in s.consumes:
                shp, dt = self._port_zero[name][c.key]
                inputs[c.key] = jax.device_put(
                    jnp.zeros(shp, dt), self._pull_shard[name][c.key])
            if s.critical:
                ct_keys = self._ct_keys(s)
                rest = {k: v for k, v in inputs.items()
                        if k not in ct_keys}
                if ct_keys:
                    cts = {k: inputs[k] for k in ct_keys}
                    outs.append(self._grad(params[name], cts, rest))
                else:
                    outs.append(self._grad(params[name], rest))
                continue
            out = self._fwd[name](params[name], inputs)
            outs.append(out)
            if s.trainable:
                # fresh zeros in the queue-pull layout: the fwd OUTPUT may
                # carry a CP/seq-sharded layout the bwd jit does not take
                cts = {p.name: jax.device_put(
                    jnp.zeros(out[p.name].shape, out[p.name].dtype),
                    self._ct_pull_shard[name][p.name])
                    for p in s.emits}
                ct_keys = self._ct_keys(s)
                if ct_keys:
                    rest = {k: v for k, v in inputs.items()
                            if k not in ct_keys}
                    outs.append(self._bwd[name](
                        params[name], {k: inputs[k] for k in ct_keys},
                        rest, cts))
                else:
                    outs.append(self._bwd[name](params[name], inputs,
                                                cts))
        # the optimizer path runs on worker threads too (the per-section
        # ``upd`` dispatch): trace + compile the ssq and AdamW-update jits
        # here with dummy (donated) state so no worker ever traces
        for name in self._trainable:
            gs = jax.tree_util.tree_map(
                lambda x, sh: jnp.zeros(x.shape, x.dtype, device=sh),
                params[name], self._p_shard[name])
            st = jax.device_put(adamw.init(params[name]),
                                self._o_shard[name])
            if name in self._compress:
                g_stacked = self._ef_init(name, params[name])
                outs.append(self._compress_step[name](
                    g_stacked, self._ef_init(name, params[name]),
                    jnp.float32(1.0)))
            outs.append(self._ssq[name](gs))
            lr = self.lr_fn(jnp.int32(0))
            if self.opt_cfg.clip_norm > 0:
                outs.append(self._update[name](gs, st, lr,
                                               jnp.float32(1.0)))
            else:
                outs.append(self._update[name](gs, st, lr))
        jax.block_until_ready(outs)

    # ------------------------------------------------------------------ #
    # planning
    # ------------------------------------------------------------------ #
    def plan_iteration(self, host: Dict[str, np.ndarray], *,
                       reorder: bool = True) -> IterationPlan:
        """Activation predicates → cost-model 6-tuples → wavefront (or
        FIFO) sample order → per-section capacity layouts."""
        assert self._built is not None, "runtime not shape-bound yet"
        flags: Dict[str, np.ndarray] = {}
        n = None
        for s in self.spec.sections:
            if s.activation is not None:
                f = np.asarray(s.activation(host)).astype(bool)
                flags[s.name] = f
                n = len(f)
        if n is None:
            n = self.B
        assert n == self.B, (n, self.B)
        if not flags:
            # homogeneous batch: every order ties — skip the scheduler
            reorder = False
        samples = cmdl.sample_tuples(self.graph, flags, self.S or 1, n=n)
        order, sched = order_samples(samples, reorder=reorder)
        activation = {name: build_activation(order, f, self.mbs)
                      for name, f in flags.items()}
        return IterationPlan(tuple(order), self.mbs, self.n_mb,
                             activation, sched)

    def _dispatched(self, plan: IterationPlan) -> Dict[str, set]:
        """Effective per-microbatch dispatch sets: a producer runs on mb
        ``i`` iff its predicate activates AND some consumer of it is
        dispatched on ``i`` (work nobody pulls is never submitted)."""
        disp: Dict[str, set] = {self._crit: set(range(plan.n_mb))}
        for name in reversed(self._topo):
            if name == self._crit:
                continue
            s = self.spec.section(name)
            avail: set = set()
            for p in s.emits:
                for c in self.spec.consumers_of(name, p.name):
                    avail |= disp.get(c, set())
            act = plan.activation.get(name)
            mine = (set(act.active_mbs) if act is not None
                    else set(range(plan.n_mb)))
            disp[name] = mine & avail
        return disp

    # ------------------------------------------------------------------ #
    # streaming state: install / state
    # ------------------------------------------------------------------ #
    def install(self, params: Dict[str, Any],
                opts: Dict[str, Any]) -> None:
        """Adopt per-section params (every section) and optimizer states
        (at least every trainable section) as the runtime's streaming
        state.  Worker-side ``upd`` tasks advance this state in place;
        read it back with :meth:`state`.  Requires a quiescent runtime
        (nothing in flight)."""
        if self._inflight:
            raise RuntimeError(
                "install() requires a quiescent runtime — retire()/"
                "drain() the in-flight iterations first")
        missing = {s.name for s in self.spec.sections} - set(params)
        if missing:
            raise ValueError(f"install: missing params for sections "
                             f"{sorted(missing)}")
        missing_o = set(self._trainable) - set(opts)
        if missing_o:
            raise ValueError(f"install: missing optimizer state for "
                             f"trainable sections {sorted(missing_o)}")
        # donation lint (repro.analysis.donation): the worker-side update
        # jits DONATE the installed optimizer state, and jax.device_put
        # is a no-copy identity when the sharding already matches — so
        # re-installing a tree a previous stream consumed, or installing
        # trees that alias each other, would crash deep inside a worker
        # jit.  Catch every such hazard here with a named error instead.
        from repro.analysis import donation as _donation
        _donation.lint_state(params, opts, runtime=self,
                             ef=self._ef).raise_on_error(
            adamw.DonatedStateError, "install: donation lint failed")
        self._params = dict(params)
        self._opts = dict(opts)
        # error-feedback residuals for compressed sections: zero-init on
        # FIRST install only, preserved across installs — the serialized
        # train_iteration wrapper installs every step, and resetting EF
        # there would silently disable the int8 residual carry
        for name in self._compress:
            if name not in self._ef:
                self._ef[name] = self._ef_init(name, self._params[name])
        self._installed = True

    def state(self) -> Tuple[Dict[str, Any], Dict[str, Any]]:
        """Snapshot of the current (params, opts) streaming state.  Only
        consistent across sections when nothing is in flight."""
        return dict(self._params), dict(self._opts)

    @property
    def in_flight(self) -> int:
        return len(self._inflight)

    # ------------------------------------------------------------------ #
    # one training iteration on the executor (streaming)
    # ------------------------------------------------------------------ #
    def submit_iteration(self, batch, step_idx, *,
                         reorder: bool = True,
                         plan: Optional[IterationPlan] = None,
                         consts: Optional[Dict[str, Dict[str, Any]]] = None,
                         return_grads: bool = False,
                         timeout: float = 300.0) -> int:
        """Enqueue one global-batch iteration onto the section streams
        and return its sequence number.  All tasks — including each
        trainable section's grad-finalize + AdamW ``upd`` — run on the
        section workers; nothing blocks here beyond the ``lookahead``
        gate (when more than ``lookahead`` iterations are already in
        flight, the oldest is retired first and its metrics buffered for
        the next :meth:`retire`)."""
        assert self._installed, \
            "call install(params, opts) before submit_iteration()"
        while len(self._inflight) > self.lookahead:
            self._retired.append(self._retire_oldest(timeout=timeout))
        host = {k: np.asarray(v) for k, v in batch.items()}
        self._ensure_built(host)
        if plan is None:
            plan = self.plan_iteration(host, reorder=reorder)
        assert plan.mbs == self.mbs and plan.n_mb == self.n_mb, \
            (plan.mbs, plan.n_mb, self.mbs, self.n_mb)
        idx = list(plan.order)
        keys = {k for s in self.spec.sections for k in s.inputs}
        ordered = {k: host[k][idx] for k in keys}
        placed_consts: Dict[str, Dict[str, Any]] = {}
        for s in self.spec.sections:
            if s.consts:
                given = (consts or {}).get(s.name, {})
                missing = set(s.consts) - set(given)
                if missing:
                    raise ValueError(
                        f"section {s.name!r}: missing consts "
                        f"{sorted(missing)}")
                rep = shd.replicated(self.rt.mesh(s.name))
                placed_consts[s.name] = {
                    k: jax.device_put(given[k], rep) for k in s.consts}
        disp = self._dispatched(plan)
        by_name = {s.name: s for s in self.spec.sections}
        m = plan.mbs
        q = self.rt.queue
        rec = _Inflight(self._it_seq, f"s{self._it_seq}", step_idx, plan,
                        return_grads, self._trainable)
        self._it_seq += 1
        it = rec.scope     # iteration-scoped tag namespace (evicted on
        #                    retirement: cross-iteration prefetch cannot
        #                    alias message keys across iterations)

        def mb_inputs(s: SectionSpec, i: int) -> Dict[str, Any]:
            rows = slice(i * m, (i + 1) * m)
            act = plan.activation.get(s.name)
            out = {}
            for k in s.inputs:
                v = ordered[k][rows]
                if act is not None:
                    v = v[act.idx[i]]
                out[k] = jax.device_put(jnp.asarray(v),
                                        self._in_shard[s.name][k])
            if act is not None:
                out["act_valid"] = jax.device_put(
                    jnp.asarray(act.valid[i]),
                    self._in_shard[s.name]["act_valid"])
            for c in s.consumes:
                sa = plan.activation.get(c.section)
                if sa is not None:
                    out[f"{c.section}.act_idx"] = jnp.asarray(sa.idx[i])
                    out[f"{c.section}.act_valid"] = jnp.asarray(
                        sa.valid[i])
            for k, v in placed_consts.get(s.name, {}).items():
                out[k] = v
            return out

        def pull_consumed(s: SectionSpec, i: int) -> Dict[str, Any]:
            pulled, stalled = {}, False
            for c in s.consumes:
                if i in disp.get(c.section, ()):
                    pulled[c.key] = q.pull(
                        c.section, s.name, f"{it}/{c.key}.{i}",
                        sharding=self._pull_shard[s.name][c.key],
                        timeout=timeout)
                    stalled = True
                else:
                    # inactive producer: the port's contribution is the
                    # exact zero a colocated step computes
                    shp, dt = self._port_zero[s.name][c.key]
                    pulled[c.key] = jax.device_put(
                        jnp.zeros(shp, dt),
                        self._pull_shard[s.name][c.key])
            if stalled:
                mark_start()      # dependency wait is idle, not busy
            return pulled

        def fwd_task(s: SectionSpec, i: int):
            def fn():
                pulled = pull_consumed(s, i)
                inputs = {**mb_inputs(s, i), **pulled}
                out = self._fwd[s.name](self._params[s.name], inputs)
                if s.trainable:
                    rec.ctx[(s.name, i)] = inputs
                for p in s.emits:
                    for cname in self.spec.consumers_of(s.name, p.name):
                        if i in disp.get(cname, ()):
                            q.push(s.name, cname,
                                   f"{it}/{s.name}.{p.name}.{i}",
                                   out[p.name])
                return out
            return fn

        def crit_task(i: int):
            s = by_name[self._crit]
            ct_keys = self._ct_keys(s)

            def fn():
                pulled = pull_consumed(s, i)
                rest = {**mb_inputs(s, i),
                        **{k: v for k, v in pulled.items()
                           if k not in ct_keys}}
                if self._grad_has_ct:
                    cts = {k: pulled[k] for k in ct_keys}
                    val, g_p, g_c = self._grad(self._params[s.name], cts,
                                               rest)
                else:
                    g_c = {}
                    val, g_p = self._grad(self._params[s.name], rest)
                loss, aux = (val if s.loss_aux else (val, None))
                for c in s.consumes:
                    if c.key in g_c and i in disp.get(c.section, ()):
                        q.push(s.name, c.section,
                               f"{it}/ct.{c.key}.{i}", g_c[c.key])
                rec.crit_acc["loss"] = rec.crit_acc["loss"] + loss
                if aux is not None:
                    a0 = rec.crit_acc["aux"]
                    rec.crit_acc["aux"] = aux if a0 is None else \
                        jax.tree_util.tree_map(lambda x, y: x + y, a0, aux)
                g0 = rec.acc[s.name]["g"]
                if g0 is None:
                    # f32 zero seed, like a colocated scan carry — seeding
                    # with the raw param-dtype grad would double-round
                    # (g_p shapes, not params: compressed sections emit
                    # stacked [dp, ...] per-shard partial grads)
                    g0 = jax.tree_util.tree_map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), g_p)
                rec.acc[s.name]["g"] = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g0, g_p)
                # block before finishing: the section mesh must be quiet
                # when another thread launches its next collective-bearing
                # program (XLA CPU rendezvous contract)
                jax.block_until_ready((rec.acc[s.name]["g"],
                                       rec.crit_acc["loss"]))
                return loss
            return fn

        def bwd_task(s: SectionSpec, i: int):
            ct_keys = self._ct_keys(s)

            def fn():
                cts = {}
                for p in s.emits:
                    consumer = self.spec.consumers_of(s.name, p.name)[0]
                    cts[p.name] = q.pull(
                        consumer, s.name, f"{it}/ct.{s.name}.{p.name}.{i}",
                        sharding=self._ct_pull_shard[s.name][p.name],
                        timeout=timeout)
                mark_start()
                inputs = rec.ctx.pop((s.name, i))
                if ct_keys:
                    rest = {k: v for k, v in inputs.items()
                            if k not in ct_keys}
                    g_p, g_c = self._bwd[s.name](
                        self._params[s.name],
                        {k: inputs[k] for k in ct_keys}, rest, cts)
                    for c in s.consumes:
                        if c.key in g_c and i in disp.get(c.section, ()):
                            q.push(s.name, c.section,
                                   f"{it}/ct.{c.key}.{i}", g_c[c.key])
                else:
                    g_p = self._bwd[s.name](self._params[s.name], inputs,
                                            cts)
                g0 = rec.acc[s.name]["g"]
                if g0 is None:
                    g0 = jax.tree_util.tree_map(
                        lambda x: jnp.zeros(x.shape, jnp.float32), g_p)
                rec.acc[s.name]["g"] = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), g0, g_p)
                jax.block_until_ready(rec.acc[s.name]["g"])
                return True
            return fn

        n_mb = plan.n_mb
        trainable = list(self._trainable)

        def upd_task(name: str):
            peers = [n for n in trainable if n != name]
            comp = self._compress.get(name)

            def fn():
                g = rec.acc[name]["g"]
                if g is None:      # section never dispatched: exact zero
                    lead = (self._comp_dp[name],) if comp else ()
                    g = jax.tree_util.tree_map(
                        lambda x: jnp.zeros(lead + x.shape, jnp.float32),
                        self._params[name])
                if comp:
                    # stacked per-shard partial grads → ONE compressed
                    # all-reduce over the data axis; the error-feedback
                    # residual threads to the next iteration
                    gs, self._ef[name] = self._compress_step[name](
                        g, self._ef[name], jnp.float32(1.0 / n_mb))
                else:
                    gs = jax.tree_util.tree_map(
                        lambda g_, p: (g_ / n_mb).astype(p.dtype), g,
                        self._params[name])
                # joint grad-norm rendezvous: every trainable section
                # pushes its per-leaf sum-of-squares vector to every peer
                # BEFORE pulling any (pushes never block → no wait cycle),
                # then all of them assemble the identical host reduction in
                # sorted-section order — bitwise the one global clip
                # threshold the colocated / main-thread finalize computed
                vec = self._ssq[name](gs)
                for p in peers:
                    q.push(name, p, f"{it}/gnorm.{name}", vec)
                vecs = {name: np.asarray(jax.device_get(vec))}
                for p in peers:
                    vecs[p] = np.asarray(jax.device_get(
                        q.pull(p, name, f"{it}/gnorm.{p}",
                               timeout=timeout)))
                if peers:
                    mark_start()   # rendezvous wait is idle, not busy
                gnorm = jnp.sqrt(jnp.sum(jnp.asarray(
                    np.concatenate([vecs[n] for n in sorted(vecs)]))))
                lr = self.lr_fn(jnp.int32(rec.step_idx))
                if self.opt_cfg.clip_norm > 0:
                    p2, o2, _ = self._update[name](gs, self._opts[name],
                                                   lr, gnorm)
                else:
                    p2, o2, _ = self._update[name](gs, self._opts[name],
                                                   lr)
                # synchronize the update program before installing: this
                # worker's next task (fwd of iteration i+1) launches the
                # next collective-bearing program on the same section mesh
                # (XLA CPU rendezvous contract)
                jax.block_until_ready((p2, o2))
                self._params[name], self._opts[name] = p2, o2
                out = {"grad_norm": gnorm, "lr": lr}
                if rec.return_grads:
                    out["grads"] = gs
                if name == self._crit:
                    out["loss"] = (rec.crit_acc["loss"]
                                   / n_mb).astype(jnp.float32)
                    if rec.crit_acc["aux"] is not None:
                        out["aux"] = jax.tree_util.tree_map(
                            lambda v: (v / n_mb).astype(jnp.float32),
                            rec.crit_acc["aux"])
                return out
            return fn

        dispatches: List[Dispatch] = []
        for name in self._topo:
            if name == self._crit:
                continue
            s = by_name[name]
            for i in sorted(disp[name]):
                dispatches.append(Dispatch(name, f"fwd{i}",
                                           fwd_task(s, i)))
        for i in range(plan.n_mb):
            dispatches.append(Dispatch(self._crit, f"mb{i}",
                                       crit_task(i)))
        for name in reversed(self._topo):
            s = by_name[name]
            if name == self._crit or not s.trainable:
                continue
            for i in sorted(disp[name]):
                dispatches.append(Dispatch(name, f"bwd{i}",
                                           bwd_task(s, i)))
        # grad-finalize + AdamW run on each trainable section's OWN worker:
        # the per-section FIFO serializes update(i) before that section's
        # fwd(i+1) while other sections stream ahead independently
        for name in self._topo:
            if by_name[name].trainable:
                dispatches.append(Dispatch(name, "upd", upd_task(name)))
        self._session.submit(rec.seq, dispatches)
        self._inflight.append(rec)
        return rec.seq

    # ------------------------------------------------------------------ #
    # retirement: collect one iteration's metrics
    # ------------------------------------------------------------------ #
    def _retire_oldest(self, *, timeout: float = 300.0) -> dict:
        rec = self._inflight.popleft()
        try:
            execution = self._session.retire(rec.seq, timeout=timeout)
        finally:
            leftovers = self.rt.queue.evict_scope(rec.scope)
            if leftovers:
                _log.warning(
                    "iteration %s retired with undrained messages "
                    "(producer pushed, no consumer pulled): %s",
                    rec.step_idx, leftovers)
        self.last_execution = execution
        upd = {n: execution.results[(n, "upd")] for n in self._trainable}
        crit = upd[self._crit]
        metrics = {"loss": crit["loss"], "grad_norm": crit["grad_norm"],
                   "lr": crit["lr"], "execution": execution,
                   "plan": rec.plan, "n_tasks": execution.task_counts}
        for k, v in crit.get("aux", {}).items():
            metrics[k] = v
        if rec.return_grads:
            metrics["grads"] = {n: upd[n]["grads"]
                                for n in self._trainable}
        return metrics

    def retire(self, *, timeout: float = 300.0) -> dict:
        """Block until the oldest outstanding iteration completes and
        return its metrics dict (loss / joint grad_norm / lr / aux
        scalars / realized ``execution`` timeline / ``plan`` /
        per-section ``n_tasks``).  Iterations auto-retired by the
        lookahead gate are returned first, in order."""
        if self._retired:
            return self._retired.popleft()
        if not self._inflight:
            raise RuntimeError("retire(): no iteration in flight")
        return self._retire_oldest(timeout=timeout)

    def drain(self, *, timeout: float = 300.0) -> List[dict]:
        """Retire every outstanding iteration (oldest first); returns
        their metrics in submission order.  Leaves the runtime quiescent
        — required before ``install()`` or shape rebinding."""
        out = []
        while self._retired:
            out.append(self._retired.popleft())
        while self._inflight:
            out.append(self._retire_oldest(timeout=timeout))
        return out

    # ------------------------------------------------------------------ #
    # serialized compatibility wrapper
    # ------------------------------------------------------------------ #
    def train_iteration(self, params, opts, batch, step_idx, *,
                        reorder: bool = True,
                        plan: Optional[IterationPlan] = None,
                        consts: Optional[Dict[str, Dict[str, Any]]] = None,
                        return_grads: bool = False,
                        timeout: float = 300.0):
        """One serialized global-batch iteration: ``install`` the given
        state, ``submit_iteration``, ``retire``, and return ``(params,
        opts, metrics)``.  Exactly the streaming path at lookahead
        depth 0 — there is no second execution mode."""
        if self._inflight or self._retired:
            raise RuntimeError(
                "train_iteration() is the serialized wrapper; it cannot "
                "interleave with in-flight submit_iteration()/retire() "
                "streams — drain() first")
        self.install(params, opts)
        self.submit_iteration(batch, step_idx, reorder=reorder, plan=plan,
                              consts=consts, return_grads=return_grads,
                              timeout=timeout)
        metrics = self.retire(timeout=timeout)
        new_params, new_opts = self.state()
        return new_params, new_opts, metrics

    # ------------------------------------------------------------------ #
    def shutdown(self):
        self.rt.shutdown()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False
