"""GossipPlan: realization-IR-driven compile planning + keyed jit cache.

One object owns schedule resolution for the whole stack.  A
:class:`GossipPlan` pattern-matches the **realization IR**
(:mod:`repro.core.topology`: ``Shifts`` / ``Matching`` / ``Dense`` /
``Identity``) instead of sniffing topology attributes, and keys every
executable by the gossip REALIZATION (never by ``step % period``, which
froze aperiodic schedules):

* ``Shifts``   -- one executable per distinct ``(self_w, shifts)`` tuple,
  each with its static shifts lowered to collective-permute HLO.  At most
  ``tau`` distinct realizations even for aperiodic one-peer orders.
* ``Matching`` -- one executable per distinct pairing, lowered to ONE
  explicit-pairs collective-permute per dtype group (needs the node
  ``mesh`` -- pass it at construction).  Periodic matching families
  (one-peer hypercube) compile ``tau`` executables; an aperiodic matching
  stream (random_match) compiles one per distinct matching it visits --
  bounded only by the run length, the price of O(1) wire bytes where the
  dense route paid O(n) every step.
* ``Dense``    -- a Static schedule bakes ``W`` into one executable; a
  time-varying dense schedule gets ONE executable taking the realized
  ``W^{(k)}`` as a traced argument, fed per step.
* ``Identity`` -- the skipped-communication executable
  (``gossip(every=k)`` off-steps share one compile with ``mix = id``).

The all-reduce warm-up phase (Corollary 3) is folded into the cache key:
``realization_key(step) == ("warmup",)`` for ``step < warmup_steps``, so a
warm-up-compiled executable can never serve post-warm-up steps or vice
versa (the phases compute different things).

Consumers hand the plan a step function of the form ``fn(mix, *args)``
where ``mix`` is the realization-bound gossip executor (what
``DecentralizedOptimizer.update_with_mix`` consumes); ``plan.step_fn(k)``
returns the compiled callable for step ``k``'s realization and
``plan.mix(k)`` the bare executor (for eager use, benchmarks, and dry-run
lowering).  :class:`CompileCache` is the underlying keyed-jit cache, also
used standalone (e.g. ``launch.serve`` caches its decode executable there).

**Overlap plans** (``overlap=True``, from ``gossip(..., overlap=True)``
optimizers) compile the one-step-delayed PIPELINED executable instead:
``mix``/``step_fn(k)`` hand the step an :class:`OverlapIO` whose
``delayed`` half applies the realization in flight at ``k`` (step k-1's)
to the state-carried packed buffer and whose ``pack`` half emits step
k's; keys gain the overlap phase (prime / flush), ``donate_argnums``
rotates the double buffer in place, and ``flush_step_fn(k)`` drains the
pipeline for checkpoints and final evaluation.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import gossip
from .cache import CompileCache
from .topology import (
    AperiodicScheduleError,
    Dense,
    Gated,
    Identity,
    Matching,
    Shifts,
    Static,
    Topology,
    full_averaging,
)

PyTree = Any

__all__ = ["CompileCache", "GossipPlan", "OverlapIO"]


def _named_after(call: Callable, fn: Callable) -> Callable:
    """``call`` renamed after the step function it wraps, so its jitted
    executable (HLO module ``jit_<name>``, the module the device trace
    shows) is ``train_step`` and not ``<lambda>``."""
    call.__name__ = call.__qualname__ = getattr(fn, "__name__", "step")
    return call


@dataclasses.dataclass(frozen=True)
class OverlapIO:
    """Gossip I/O bundle for one overlapped (delayed-mix) step.

    Handed to pipelined step functions in place of the synchronous ``mix``
    executor: ``pack(payload)`` packs this step's pre-mix payload into the
    in-flight wire buffers (the double buffer carried as optimizer state),
    and ``delayed(template, bufs)`` permutes + combines the PREVIOUS
    step's buffers with ``realization`` -- the permute reads only the
    buffers, so XLA schedules it under the current step's compute.
    ``realization is None`` marks the priming step (nothing in flight:
    ``delayed`` must not be called)."""

    realization: Any            # in-flight IR node (None at the prime step)
    compression: str | None = None
    mesh: Any = None
    specs: Any = None
    axis_name: str = "node"

    @property
    def prime(self) -> bool:
        return self.realization is None

    def pack(self, payload: PyTree) -> tuple:
        return gossip.pack_payload(payload, mesh=self.mesh,
                                   axis_name=self.axis_name,
                                   specs=self.specs)

    def delayed(self, template: PyTree, bufs) -> PyTree:
        if self.prime:
            raise ValueError("priming step has no in-flight payload to mix")
        return gossip.delayed_mix(template, bufs, self.realization,
                                  compression=self.compression,
                                  mesh=self.mesh, axis_name=self.axis_name,
                                  specs=self.specs)


@dataclasses.dataclass
class GossipPlan:
    """Realization resolution + compile cache for one (topology, phase
    schedule, compression) triple.

    ``fn(mix, *args)`` is the function compiled per realization; bind it at
    construction or via :meth:`bind`.  ``warmup_steps`` / ``compression`` /
    ``every`` normally come from the optimizer (see :meth:`for_optimizer`).
    ``mesh`` (a ``jax.sharding.Mesh`` whose ``node`` axis matches ``n``)
    selects the shard-native engine for every ``Shifts``/``Matching``
    round: pack, permute, quantize and combine all run inside ``shard_map``
    over the full mesh, moving only per-shard bytes.  ``specs`` refines the
    shard_map boundary on multi-axis meshes: a PartitionSpec pytree
    matching the gossip payload, or a callable ``payload -> spec pytree``
    (``launch.sharding.gossip_payload_spec_fn`` reapplies the parameter
    placement rules); None means node-sharded leading axis with replicated
    inner dims.  Without a mesh, matchings fall back to a local gather and
    shifts to the global packed roll path.
    """

    topology: Topology
    warmup_steps: int = 0
    compression: str | None = None
    fn: Callable | None = None
    mesh: Any = None
    specs: Any = None
    every: int = 1
    max_compiles: int = 256
    # Overlapped (delayed-mix) pipeline: ``step_fn(t)`` compiles the
    # PIPELINED executable -- it mixes step t-1's in-flight payload and
    # packs step t's -- with compile keys carrying the overlap phase
    # ("prime" at the pipeline start, "flush" for checkpoint drains).
    overlap: bool = False
    # ``fn``'s argument positions whose buffers the compiled executable may
    # reuse in place (jax.jit donate_argnums, shifted past the mix arg):
    # the overlap pipeline donates params + optimizer state so the double
    # buffer is rotated, not copied.
    donate_argnums: tuple = ()
    # ``flush_fn(io, *args)`` drains the in-flight buffer (overlap plans
    # only); ``for_optimizer`` binds the optimizer's ``flush_pending``.
    flush_fn: Callable | None = None
    # jit sharding annotations, applied to EVERY compiled executable
    # (pytrees matching ``fn``'s post-mix argument/output structure) --
    # plans own the whole jit contract, so launch code lowers via
    # ``plan.lowered`` instead of wrapping its own jax.jit.  Wrapper
    # executables with leading traced-weight arguments get an
    # unconstrained slot prepended automatically.
    in_shardings: Any = None
    out_shardings: Any = None
    # Data-dependent schedule: compile ONE executable whose schedule
    # position is a TRACED optimizer-state value (``gossip.mix_scheduled``)
    # -- the mix executor takes ``mix(t, pos, gate=None, ...)`` and the
    # position advances only on rounds that actually communicate,
    # generalizing ``every=k`` to runtime skip decisions.
    scheduled: bool = False

    def __post_init__(self):
        # LRU-bounded: periodic schedules have a tiny working set and never
        # evict; an aperiodic Matching stream (random_match) compiles one
        # executable per distinct pairing it visits -- the price of O(1)
        # wire bytes where the dense route paid O(n) -- and the bound keeps
        # host memory flat over arbitrarily long runs.
        self._cache = CompileCache(max_entries=self.max_compiles)
        if self.compression:
            types = self.topology.realization_types()
            if not types <= {Shifts, Matching, Identity}:
                # int8 wire quantization exists for the permute paths
                # (gossip.mix_shifts / mix_matching); dense-matrix mixing
                # has no quantized implementation -- refuse rather than
                # silently send f32.
                raise ValueError(
                    f"compression={self.compression!r} needs shift- or "
                    f"matching-structured realizations; "
                    f"{self.topology.name!r} mixes via dense matrices "
                    f"({sorted(t.__name__ for t in types)})")
        if self.overlap:
            types = self.topology.realization_types()
            # a time-varying Dense stream compiles through ONE traced-W
            # executable, but OverlapIO closes over a static realization;
            # caching the pipelined executable under a shared "dense" key
            # would freeze the first W.  The overlap pipeline targets the
            # one-permute wire path anyway.
            if Dense in types and not isinstance(self.topology.schedule,
                                                 Static):
                raise ValueError(
                    f"overlap=True supports Shifts/Matching/Identity (and "
                    f"static Dense) realizations; {self.topology.name!r} "
                    "realizes time-varying dense matrices -- use a "
                    "permute-structured family (one_peer_exp, ceca, "
                    "base_k(k=1), random_match)")
        if self.scheduled:
            if self.overlap:
                raise ValueError(
                    "scheduled=True (data-dependent skip) cannot combine "
                    "with the overlap pipeline: the in-flight realization "
                    "would depend on a traced gate")
            if self.warmup_steps:
                raise ValueError(
                    "scheduled=True cannot combine with the all-reduce "
                    "warm-up phase: the warm-up executor takes no traced "
                    "schedule position")
            if self.every > 1:
                raise ValueError(
                    "scheduled=True generalizes every=k (the traced gate "
                    "decides which rounds communicate); set one, not both")
            if not self.topology.schedule.is_periodic:
                raise AperiodicScheduleError(
                    f"scheduled=True needs a periodic schedule "
                    f"(lax.switch over the period), but "
                    f"{self.topology.name!r} carries "
                    f"{self.topology.schedule!r}")

    @classmethod
    def for_optimizer(cls, opt, fn: Callable | None = None,
                      mesh=None, specs=None,
                      donate_argnums: tuple = (),
                      in_shardings=None, out_shardings=None) -> "GossipPlan":
        """Plan matching a chain-built optimizer's topology, warm-up phase,
        wire compression, communication interval, data-dependent schedule
        (``gossip(when=...)`` -> ``scheduled=True``), and overlap pipeline
        (whose flush executor is bound to the optimizer's
        ``flush_pending``)."""
        overlap = bool(getattr(opt, "overlap", False))
        flush_fn = None
        if overlap:
            def flush_fn(io, params, state):
                return opt.flush_pending(params, state, io)
        return cls(opt.topology, warmup_steps=opt.warmup_steps,
                   compression=opt.compression, fn=fn, mesh=mesh,
                   specs=specs, every=getattr(opt, "gossip_every", 1),
                   overlap=overlap, donate_argnums=tuple(donate_argnums),
                   flush_fn=flush_fn, in_shardings=in_shardings,
                   out_shardings=out_shardings,
                   scheduled=bool(getattr(opt, "scheduled_gossip", False)))

    def bind(self, fn: Callable) -> "GossipPlan":
        """Same plan parameters with ``fn`` bound (fresh compile cache)."""
        return dataclasses.replace(self, fn=fn)

    # -- classification -------------------------------------------------------

    def realization(self, step: int):
        """The realization IR node step ``step`` executes (including the
        ``every=k`` skipped rounds, which realize as ``Identity``)."""
        k = int(step)
        if self.every > 1:
            if k % self.every:
                return Identity()
            k //= self.every
        return self.topology.realization(k)

    @property
    def regime(self) -> str:
        """Human-readable classification of the realization types."""
        types = self.topology.realization_types()
        if types == {Dense}:
            return ("static" if isinstance(self.topology.schedule, Static)
                    else "dense")
        if types <= {Shifts, Identity}:
            return "shifts"
        if types <= {Matching, Identity}:
            return "matching"
        return "mixed" if Dense in types else "shifts+matching"

    def realization_key(self, step: int) -> tuple:
        """Hashable compile-cache key for ``step``'s executable.

        Overlap plans key the PIPELINED executable by the in-flight
        realization (step t mixes step t-1's payload), with the overlap
        phase folded in: ``("overlap", "prime")`` for the pipeline's first
        step (nothing in flight yet), ``("overlap", ...)`` thereafter --
        a primed and an un-primed executable compute different things and
        carry different state structures, so they may never be confused."""
        k = int(step)
        if self.overlap:
            if k == 0:
                return ("overlap", "prime")
            return ("overlap",) + self._key_for(k - 1)
        return self._key_for(k)

    def _key_for(self, k: int) -> tuple:
        """Phase/realization key ignoring the overlap pipelining shift.

        Classification is STRUCTURE-based (``Realization.structure_key``):
        static-weight nodes key by values -- byte-identical to the
        historical keys, so caches and HLO are unchanged -- while traced-
        weight nodes key by wire structure only, so a whole pool of
        runtime-weighted matchings shares ONE executable (the weights ride
        as traced arguments, see :meth:`_weighted_executable`)."""
        if self.warmup_steps and k < self.warmup_steps:
            return ("warmup",)
        if self.scheduled:
            return ("scheduled",)
        r = self.realization(k)
        if isinstance(r, Dense):
            if not r.traced and isinstance(self.topology.schedule, Static):
                return ("static",)
            return ("dense",)   # time-varying / traced: one traced-W exec
        return r.structure_key()

    @property
    def num_compiled(self) -> int:
        return len(self._cache)

    def cache_stats(self) -> dict:
        """Hit/miss/eviction counters of the underlying executable cache
        (an aperiodic schedule that keeps missing is recompiling per
        round; a steady-state plan should hit after warmup)."""
        return self._cache.stats()

    # -- executors ------------------------------------------------------------

    def mix(self, step: int):
        """The bare gossip executor for ``step``'s realization (static:
        every schedule decision is resolved here, outside any trace).
        Overlap plans return the step's :class:`OverlapIO` bundle instead
        of a plain callable -- same slot, pipelined contract."""
        if self.overlap:
            return self.overlap_io(step)
        k = int(step)
        mesh, specs = self.mesh, self.specs
        if self.warmup_steps and k < self.warmup_steps:
            top_full = full_averaging(self.topology.n)
            return lambda t: gossip.mix(t, top_full, 0, mesh=mesh,
                                        specs=specs)
        if self.scheduled:
            return self._scheduled_mix()
        r = self.realization(k)
        if isinstance(r, Dense) and not r.traced:
            return lambda t: gossip.mix_dense(t, r.W, mesh=mesh,
                                              specs=specs)
        comp = self.compression
        # forwards meta=/edge_weight=/node_gate= so transform hooks
        # (weights_from, deadline_skip) reach the runtime combine
        return lambda t, **kw: gossip.mix_realization(
            t, r, compression=comp, mesh=mesh, specs=specs, **kw)

    def _scheduled_mix(self):
        """The traced-position mix executor: ``mix(t, pos, gate=None,
        **kw)`` (see :func:`repro.core.gossip.mix_scheduled`)."""
        top, comp = self.topology, self.compression
        mesh, specs = self.mesh, self.specs
        return lambda t, pos, gate=None, **kw: gossip.mix_scheduled(
            t, top, pos, gate, compression=comp, mesh=mesh, specs=specs,
            **kw)

    def _jit_kwargs(self, extra_leading: int = 0) -> dict:
        """jit options every executable shares: donation and the plan-owned
        sharding annotations, both shifted past ``extra_leading`` wrapper
        arguments (the traced-W / traced-weights slot, left unconstrained)."""
        kw: dict = {}
        if self.donate_argnums:
            kw["donate_argnums"] = tuple(i + extra_leading
                                         for i in self.donate_argnums)
        if self.in_shardings is not None:
            ins = tuple(self.in_shardings)
            if extra_leading:
                ins = (None,) * extra_leading + ins
            kw["in_shardings"] = ins
        if self.out_shardings is not None:
            kw["out_shardings"] = self.out_shardings
        return kw

    def _weighted_executable(self, key: tuple, template):
        """ONE jitted executable per realization STRUCTURE: the traced
        weights (and gate) arrive as the leading argument tuple and
        ``with_weights`` rebinds them onto the structure template inside
        the trace -- a pool of differently-weighted same-structure rounds
        never retraces."""
        fn = self._require_fn()
        comp, mesh, specs = self.compression, self.mesh, self.specs

        def build():
            def call(wvals, *a):
                r = template.with_weights(wvals)
                return fn(lambda t, **kw: gossip.mix_realization(
                    t, r, compression=comp, mesh=mesh, specs=specs, **kw),
                    *a)
            return jax.jit(_named_after(call, fn),
                           **self._jit_kwargs(extra_leading=1))

        return self._cache.get(key, build)

    def overlap_io(self, step: int) -> "OverlapIO":
        """The :class:`OverlapIO` bundle for pipelined step ``step``: its
        ``delayed`` half applies the realization IN FLIGHT at that step
        (step - 1's, through the warm-up and ``every=k`` phases; ``None``
        at the priming step 0)."""
        k = int(step) - 1
        if k < 0:
            return OverlapIO(None, None, self.mesh, self.specs)
        if self.warmup_steps and k < self.warmup_steps:
            # exact-averaging warm-up rounds intentionally skip wire
            # compression, like the synchronous warm-up executor
            r = full_averaging(self.topology.n).realization(0)
            return OverlapIO(r, None, self.mesh, self.specs)
        return OverlapIO(self.realization(k), self.compression,
                         self.mesh, self.specs)

    def _dense_executable(self):
        """The time-varying dense regime's single jitted fn, taking the
        realized ``W^{(k)}`` as its leading traced argument."""
        fn = self._require_fn()
        return self._cache.get(("dense",), lambda: jax.jit(
            _named_after(lambda W, *a: fn(
                (lambda t: gossip.mix_dense(t, W)), *a), fn),
            **self._jit_kwargs(extra_leading=1)))

    def _realized_W(self, step: int) -> jax.Array:
        return jnp.asarray(self.realization(int(step)).dense(self.topology.n),
                           jnp.float32)

    def step_fn(self, step: int, *, prime: bool = False) -> Callable:
        """Compiled ``fn`` for ``step``'s realization.

        Same realization -> the SAME executable (compiled once); the
        time-varying dense regime returns a per-step wrapper feeding the
        realized ``W^{(k)}`` into one shared traced-``W`` executable.

        Overlap plans compile the PIPELINED executable: it applies step
        ``step - 1``'s realization to the in-flight buffer and packs this
        step's payload (with ``donate_argnums`` the state's double buffer
        is rotated in place, never copied).  ``prime=True`` forces the
        priming executable at ``step > 0`` -- the re-entry step after
        resuming from a FLUSHED checkpoint, whose state carries no
        in-flight buffer."""
        if self.overlap:
            fn = self._require_fn()
            if prime or int(step) == 0:
                key: tuple = ("overlap", "prime")
                io = OverlapIO(None, None, self.mesh, self.specs)
            else:
                key = self.realization_key(step)
                io = self.overlap_io(step)
            return self._cache.get(key, lambda: jax.jit(
                _named_after(lambda *a: fn(io, *a), fn),
                **self._jit_kwargs()))
        key = self.realization_key(step)
        if key == ("dense",):
            jitted = self._dense_executable()
            W = self._realized_W(step)
            return lambda *a: jitted(W, *a)
        fn = self._require_fn()
        k = int(step)
        if not (self.warmup_steps and k < self.warmup_steps) \
                and not self.scheduled:
            r = self.realization(k)
            if getattr(r, "traced", False):
                # runtime-valued round: ONE executable per structure, the
                # weights fed as the leading traced argument
                jitted = self._weighted_executable(key, r)
                wvals = r.weight_values()
                return lambda *a: jitted(wvals, *a)
        mix = self.mix(step)
        return self._cache.get(key, lambda: jax.jit(
            _named_after(lambda *a: fn(mix, *a), fn), **self._jit_kwargs()))

    def flush_step_fn(self, step: int) -> Callable:
        """Compiled drain of the overlap pipeline at python step ``step``:
        applies the realization in flight (step - 1's) to ``flush_fn``'s
        arguments and clears the buffer.  Pure -- checkpoint flows call it
        on a copy of the live state (flush-on-save) or right before the
        final evaluation.  Identity passthrough for non-overlap plans and
        at the un-primed step 0."""
        if not self.overlap or int(step) == 0:
            return lambda *a: a
        if self.flush_fn is None:
            raise ValueError(
                "overlap plan has no flush_fn bound; construct via "
                "for_optimizer or pass flush_fn=...")
        key = ("overlap", "flush") + self._key_for(int(step) - 1)
        io = self.overlap_io(step)
        flush = self.flush_fn
        return self._cache.get(key, lambda: jax.jit(
            _named_after(lambda *a: flush(io, *a), flush)))

    def lowered(self, step: int, *args):
        """``jax.jit(...).lower(*args)`` for ``step``'s executable -- for
        HLO inspection and dry-run cost analysis (args may be
        ``ShapeDtypeStruct``s, carrying shardings if desired)."""
        key = self.realization_key(step)
        if key == ("dense",):
            return self._dense_executable().lower(self._realized_W(step),
                                                  *args)
        k = int(step)
        if not (self.warmup_steps and k < self.warmup_steps) \
                and not self.scheduled and not self.overlap:
            r = self.realization(k)
            if getattr(r, "traced", False):
                return self._weighted_executable(key, r).lower(
                    r.weight_values(), *args)
        return self.step_fn(step).lower(*args)

    def _require_fn(self) -> Callable:
        if self.fn is None:
            raise ValueError(
                "GossipPlan has no bound step function; construct with "
                "fn=... or use plan.bind(fn)")
        return self.fn
