"""Alignment backend registry.

A *backend* is one way to evaluate a batch of WFA problems on device.  The
engine (``core.engine``) is backend-agnostic: it plans buckets, sizes the
static ``(s_max, k_max)`` buffers, caches executables and recovers overflow
pairs, then hands each rectangular batch to whatever backend the user named.
New strategies (bidirectional, banded, a new kernel) plug in with
:func:`register_backend` and never touch the engine.

Contract — a backend callable has the signature::

    fn(pattern, text, plen, tlen, *, pen, s_max, k_max, **extra) -> WFAResult

with ``pattern``/``text`` ``[B, L]`` int32 device/host arrays, ``plen``/
``tlen`` ``[B]`` int32, and static ``pen``/``s_max``/``k_max``.  It must be
jit-traceable (the engine compiles one executable per bucket shape around
it).

The contract has two *scoring axes* (``core.scoring``):

* ``pen`` may be any :class:`~repro.core.scoring.PenaltyModel` (or a legacy
  gap-affine ``Penalties`` triple).  ``BackendSpec.models`` names the
  recurrence kinds a backend serves (``"affine"`` / ``"linear"``); the four
  built-ins serve both (their solvers statically specialize per model),
  while plug-ins default to affine-only until they declare otherwise.
* a backend that also understands **wavefront heuristics** takes a ``heur``
  keyword (a :class:`~repro.core.scoring.WavefrontHeuristic`, static).  The
  engine only passes ``heur`` when a non-exact heuristic is requested, so
  heuristic-unaware plug-ins keep working for exact alignment and fail
  loudly (not wrongly) when pruning is asked of them.

Every backend serves two *output modes* (the engine's
``output="score" | "cigar"``):

* ``fn`` — the score-only throughput path;
* ``trace_variant`` — same signature, but the returned ``WFAResult`` also
  carries a trace that ``core.cigar`` can turn into exact CIGARs: either
  the full offset history (``m_hist``/``i_hist``/``d_hist``) or the ~16x
  smaller packed 2-bit provenance words (``m_bt``/``i_bt``/``d_bt``; the
  I/D planes are ``None`` for linear models).  ``supports_cigar`` is
  simply "has a trace variant"; score-only plug-ins may omit it.

The breakpoint waves of ``trace_variant="bidir"`` belong to no backend:
the engine runs the jnp meet solver ``wf.wfa_bidir_meet`` for them on
every backend and platform.

Backends that shard over a device mesh set ``needs_mesh`` and receive the
engine's ``mesh`` as a keyword (an engine given none spans every local
device).  Two further hooks tune how the engine
*drives* a backend (both optional):

* ``donate_args`` — positional indices of ``(pattern, text, plen, tlen)``
  whose device buffers may be donated to the executable
  (``jit(donate_argnums=...)``).  On GPU/TPU this lets XLA alias the
  ``[B]`` int32 score output onto a spent input buffer, so a streaming
  session's double-buffered waves don't accumulate dead input allocations.
  Ignored on CPU (donation is unsupported there).
* ``dispatch`` — ``dispatch(exe_fn, *arrays) -> WFAResult`` intercepts the
  jitted call itself.  The engine and the streaming session route every
  wave through it, so a backend can split a wave across streams, add
  tracing, or stage inputs its own way without touching engine code.

Built-ins (all CIGAR-capable, all serving every penalty model and
heuristic):

* ``"ref"``      — pure-jnp WFA; trace variant keeps the full offset
                   history (the memory-hungry oracle path)
* ``"ring"``     — rolling-window pure-jnp WFA; trace variant records the
                   packed backtrace alongside the rings
* ``"kernel"``   — the Pallas TPU kernel (interpret=True on CPU, with
                   the chip's fetch); trace variant OR-accumulates packed
                   words in VMEM
* ``"shardmap"`` — the ``kernel`` backend per shard inside ``shard_map``
                   over every device of the mesh (per-shard termination,
                   zero collectives — the paper's "no inter-DPU
                   communication"); each shard returns its own loop
                   counters, and its trace variant its own packed words
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.core import scoring
from repro.core import wavefront as wf

ALL_MODELS = ("affine", "linear")


def _accepts_kw(fn: Optional[Callable], kw: str) -> bool:
    """True when ``fn`` takes keyword ``kw`` (or ``**kwargs``)."""
    if fn is None:
        return False
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):    # builtins / odd callables: assume yes
        return True
    if kw in sig.parameters:
        return True
    return any(p.kind is inspect.Parameter.VAR_KEYWORD
               for p in sig.parameters.values())


def _accepts_heur(fn: Optional[Callable]) -> bool:
    """True when ``fn`` takes a ``heur`` keyword (or ``**kwargs``)."""
    return _accepts_kw(fn, "heur")


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    fn: Callable[..., wf.WFAResult]
    trace_variant: Optional[Callable[..., wf.WFAResult]] = None
    needs_mesh: bool = False
    donate_args: Tuple[int, ...] = ()
    dispatch: Optional[Callable[..., wf.WFAResult]] = None
    models: Tuple[str, ...] = ("affine",)
    doc: str = ""

    @property
    def supports_cigar(self) -> bool:
        return self.trace_variant is not None

    def supports_model(self, kind: str) -> bool:
        return kind in self.models

    def accepts_heuristic(self, output: str = "score") -> bool:
        """Whether the callable serving ``output`` takes ``heur=``."""
        return _accepts_heur(self.fn if output == "score"
                             else self.trace_variant)

    def callables(self) -> Tuple[Callable, ...]:
        """Every non-None solver callable this backend exposes (used by the
        engine to validate ``backend_opts`` keys up front)."""
        return tuple(f for f in (self.fn, self.trace_variant)
                     if f is not None)

    def accepts_states(self) -> bool:
        """Whether the trace variant takes ``begin_state``/``end_state``
        (the BiWFA recursion's boundary-constrained sub-alignments).  The
        engine silently substitutes the ``ring`` trace path for stateful
        children on backends that don't."""
        return _accepts_kw(self.trace_variant, "begin_state")

    def variant(self, output: str,
                model_kind: str = "affine") -> Callable[..., wf.WFAResult]:
        """The callable serving one output mode ('score' or 'cigar') under
        one penalty-model recurrence kind ('affine' or 'linear')."""
        if model_kind not in self.models:
            raise ValueError(
                f"backend {self.name!r} serves penalty models "
                f"{self.models}; {model_kind!r} models need one of: "
                f"{model_backends(model_kind)}")
        if output == "score":
            return self.fn
        if output == "cigar":
            if self.trace_variant is None:
                raise ValueError(
                    f"backend {self.name!r} is score-only (no trace "
                    f"variant); CIGAR-capable backends: "
                    f"{cigar_backends()}")
            return self.trace_variant
        raise ValueError(f"unknown output mode {output!r}; "
                         "use 'score' or 'cigar'")


_REGISTRY: Dict[str, BackendSpec] = {}


def register_backend(name: str, fn: Optional[Callable] = None, *,
                     trace_variant: Optional[Callable] = None,
                     supports_cigar: bool = False,
                     needs_mesh: bool = False,
                     donate_args: Tuple[int, ...] = (),
                     dispatch: Optional[Callable] = None,
                     models: Tuple[str, ...] = ("affine",),
                     doc: str = ""):
    """Register an alignment backend (usable as a decorator).

    Re-registering a name replaces the previous entry (useful for tests and
    for swapping in tuned variants).  ``models`` declares the penalty-model
    recurrence kinds the backend serves (plug-ins default to affine-only;
    pass ``models=("affine", "linear")`` when the backend handles linear
    models too).  ``supports_cigar=True`` is the deprecated pre-output-mode
    spelling for backends whose ``fn`` itself returns a traceback-capable
    ``WFAResult`` (full history, like the old ``ref``): it makes ``fn``
    double as the trace variant.
    """
    def _add(f):
        tv = trace_variant
        if tv is None and supports_cigar:
            tv = f
        _REGISTRY[name] = BackendSpec(name=name, fn=f,
                                      trace_variant=tv,
                                      needs_mesh=needs_mesh,
                                      donate_args=tuple(donate_args),
                                      dispatch=dispatch,
                                      models=tuple(models),
                                      doc=doc or (f.__doc__ or "").strip())
        return f

    if fn is not None:
        return _add(fn)
    return _add


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_backend(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown alignment backend {name!r}; "
                       f"available: {available_backends()}") from None


def available_backends() -> List[str]:
    return sorted(_REGISTRY)


def cigar_backends() -> List[str]:
    """Backends with a trace variant (serve ``output='cigar'``)."""
    return sorted(n for n, s in _REGISTRY.items() if s.supports_cigar)


def model_backends(kind: str) -> List[str]:
    """Backends serving penalty models of recurrence ``kind``."""
    return sorted(n for n, s in _REGISTRY.items() if s.supports_model(kind))


# ---------------------------------------------------------------------------
# Built-in backends.


def _ref_trace(pattern, text, plen, tlen, *, pen, s_max, k_max, heur=None,
               begin_state="M", end_state="M"):
    return wf.wfa_forward(pattern, text, plen, tlen, pen=pen,
                          s_max=s_max, k_max=k_max, keep_history=True,
                          heur=heur, begin_state=begin_state,
                          end_state=end_state)


@register_backend("ref", trace_variant=_ref_trace, models=ALL_MODELS,
                  doc="pure-jnp WFA; full-history CIGAR traceback")
def _ref_backend(pattern, text, plen, tlen, *, pen, s_max, k_max, heur=None):
    return wf.wfa_forward(pattern, text, plen, tlen, pen=pen,
                          s_max=s_max, k_max=k_max, keep_history=False,
                          heur=heur)


def _ring_trace(pattern, text, plen, tlen, *, pen, s_max, k_max, heur=None,
                begin_state="M", end_state="M", band_cap=None):
    return wf.wfa_scores_packed(pattern, text, plen, tlen, pen=pen,
                                s_max=s_max, k_max=k_max, heur=heur,
                                begin_state=begin_state, end_state=end_state,
                                band_cap=band_cap)


# The [B] int32 length buffers are donatable: the [B] int32 score output
# can alias one of them, so streamed waves recycle device memory.
@register_backend("ring", donate_args=(2, 3), trace_variant=_ring_trace,
                  models=ALL_MODELS,
                  doc="rolling-window pure-jnp WFA; packed backtrace")
def _ring_backend(pattern, text, plen, tlen, *, pen, s_max, k_max, heur=None,
                  band_cap=None):
    return wf.wfa_scores(pattern, text, plen, tlen, pen=pen,
                         s_max=s_max, k_max=k_max, heur=heur,
                         band_cap=band_cap)


def _kernel_trace(pattern, text, plen, tlen, *, pen, s_max, k_max,
                  heur=None, block_pairs=None, char_bits=32, band_cap=None):
    from repro.kernels.wfa import ops as kops  # lazy: pallas import is heavy
    score, m_bt, i_bt, d_bt, steps, trips = kops.wfa_align_trace(
        pattern, text, plen, tlen, pen=pen, s_max=s_max, k_max=k_max,
        heur=heur, block_pairs=block_pairs, char_bits=char_bits,
        band_cap=band_cap, counts=True)
    return wf.WFAResult(score, None, None, None, steps, m_bt, i_bt, d_bt,
                        n_ext_trips=trips)


@register_backend("kernel", donate_args=(2, 3), trace_variant=_kernel_trace,
                  models=ALL_MODELS,
                  doc="Pallas TPU kernel (interpret on CPU); packed "
                      "backtrace in VMEM")
def _kernel_backend(pattern, text, plen, tlen, *, pen, s_max, k_max,
                    heur=None, block_pairs=None, char_bits=32, band_cap=None):
    from repro.kernels.wfa import ops as kops  # lazy: pallas import is heavy
    score, steps, trips = kops.wfa_align(
        pattern, text, plen, tlen, pen=pen, s_max=s_max, k_max=k_max,
        heur=heur, block_pairs=block_pairs, char_bits=char_bits,
        band_cap=band_cap, counts=True)
    return wf.WFAResult(score, None, None, None, steps, n_ext_trips=trips)


def _per_shard(kernel_fn, n_bt: int, mesh, pattern, text, plen, tlen, **kw):
    """A ``kernel`` backend callable on each shard under ``shard_map``.

    The pair axis of every input and output is split over all of
    ``mesh``'s axes (``engine.pair_sharding``); each shard's steps and
    trips come back as its row of a [shards] array, and the first
    ``n_bt`` packed backtrace planes ([words, B, K]) split on their pair
    axis.  No collectives: each shard's loops end with its own pairs.
    """
    from jax.sharding import PartitionSpec as P
    names = tuple(mesh.axis_names)
    rows, cols = P(names), P(names, None)

    def local(p, t, pl, tl):
        r = kernel_fn(p, t, pl, tl, **kw)
        return ((r.score, r.n_steps[None], r.n_ext_trips[None])
                + (r.m_bt, r.i_bt, r.d_bt)[:n_bt])

    # the kernel's loops are per shard by construction, so the
    # varying-manual-axes check is off
    score, steps, trips, *bt = jax.shard_map(
        local, mesh=mesh, in_specs=(cols, cols, rows, rows),
        out_specs=(rows, rows, rows) + (P(None, names, None),) * n_bt,
        check_vma=False)(pattern, text, plen, tlen)
    return wf.WFAResult(score, None, None, None, steps,
                        *(bt + [None] * (3 - n_bt)), n_ext_trips=trips)


def _shardmap_trace(pattern, text, plen, tlen, *, pen, s_max, k_max, mesh,
                    heur=None, block_pairs=None, char_bits=32, band_cap=None):
    # linear models record one M plane, affine ones M, I and D
    n_bt = 3 if scoring.as_model(pen).kind == "affine" else 1
    return _per_shard(_kernel_trace, n_bt, mesh, pattern, text, plen, tlen,
                      pen=pen, s_max=s_max, k_max=k_max, heur=heur,
                      block_pairs=block_pairs, char_bits=char_bits,
                      band_cap=band_cap)


@register_backend("shardmap", needs_mesh=True, trace_variant=_shardmap_trace,
                  models=ALL_MODELS,
                  doc="the kernel backend per shard in shard_map: per-shard "
                      "termination, zero collectives; per-shard loop "
                      "counters and packed backtrace")
def _shardmap_backend(pattern, text, plen, tlen, *, pen, s_max, k_max, mesh,
                      heur=None, block_pairs=None, char_bits=32,
                      band_cap=None):
    return _per_shard(_kernel_backend, 0, mesh, pattern, text, plen, tlen,
                      pen=pen, s_max=s_max, k_max=k_max, heur=heur,
                      block_pairs=block_pairs, char_bits=char_bits,
                      band_cap=band_cap)
