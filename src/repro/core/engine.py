"""`AlignmentEngine` — the unified alignment façade.

The paper's throughput comes from keeping thousands of independent WFA
problems saturating the hardware with minimal host<->device overhead.  This
module owns every policy decision on that path, in one place:

* **backend registry** (``core.backends``) — ``ref`` / ``ring`` / ``kernel``
  / ``shardmap`` (and user plug-ins via ``register_backend``) are looked up
  by name; the engine never hard-codes a dispatch chain.
* **length-bucketed batching** — pairs are grouped by the power of two of
  ``max(plen, tlen)``, so short reads stop paying the longest pair's padded
  ``K`` band and score loop.  Each bucket gets its own static
  ``(L, s_max, k_max)`` problem shape.
* **executable caching** — compiled executables are cached per
  ``(backend, penalties, batch-shape, bounds)``.  Bucket dims are quantized
  (power-of-two lengths and pair counts, ``s_max`` rounded up) precisely so
  that serving-time traffic keeps hitting the same few shapes: repeated
  ``align()`` calls re-trace nothing.
* **adaptive two-pass bounds** — pass 1 runs with the optimistic
  ``edit_frac``-derived ``s_max`` (the paper's E-threshold regime); pairs
  that come back unresolved (``score == -1``) are re-run with the exact
  worst-case bound (the BIMSA "CPU recovery" analogue), so the common case
  stays fast while every pair still terminates with a true score.

The engine also owns the PIM phase accounting (scatter / kernel / gather
bytes and seconds — Fig. 1's *Total vs Kernel* decomposition) that used to
live in ``core.pim``.  ``WFAligner`` and ``PIMBatchAligner`` are thin
wrappers kept for compatibility.

Execution itself lives in ``core.session``: every ``align()`` call is one
blocking pass through an :class:`~repro.core.session.AlignmentSession`, and
``engine.stream()`` opens the same session in pipelined mode — async
``submit()``, host packing overlapped with in-flight device kernels, and
out-of-order ``as_completed()`` gather (the paper's transfer/compute
overlap, the 4.87x-vs-37.4x gap).

Every entry point takes an **output mode** — ``output="score"`` (the
default; throughput path) or ``output="cigar"`` (full alignments).  CIGAR
mode compiles each backend's *trace variant* (``core.backends``): ``ref``
keeps the full offset history, while ``ring``/``kernel``/``shardmap``
record the ~16x smaller packed 2-bit backtrace, so every backend emits
exact CIGARs — including pairs that overflow the optimistic bound and
re-run through the exact-bound recovery pass.

Quickstart::

    from repro.core.engine import AlignmentEngine

    eng = AlignmentEngine(backend="ring", edit_frac=0.04)
    res = eng.align(["ACGT...", ...], ["ACGA...", ...])
    res.scores        # [B] exact gap-affine costs (Gotoh-identical)
    res.stats         # buckets, cache hits, overflow recoveries, PIM phases

    full = eng.align(patterns, texts, output="cigar")
    full.cigar_strings()             # SAM 1.4 "="/"X" run-length CIGARs
    full.cigar_strings("classic")    # pre-1.4 "M" CIGARs

    from repro.core.scoring import Edit, AdaptiveBand
    eng.align(patterns, texts, penalties=Edit())        # Levenshtein mode
    eng.align(patterns, texts, heuristic=AdaptiveBand())  # WFA-adaptive
                                     # pruning; result.approximate == True

    with eng.stream(max_inflight_waves=2) as sess:   # pipelined serving
        tickets = [sess.submit(ps, ts, output="cigar") for ps, ts in chunks]
        for ticket in sess.as_completed():           # out-of-order gather
            consume(ticket.result().cigars)
"""
from __future__ import annotations

import dataclasses
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import cigar as cigar_mod
from repro.core import scoring
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.core import wavefront as wf
from repro.core.backends import BackendSpec, get_backend, _accepts_kw
from repro.core.penalties import DEFAULT
from repro.distributed.compat import make_mesh

Seq = Union[str, bytes, np.ndarray]

# Registry counter of host seconds spent in executables' first calls,
# where JAX traces and compiles (or reads its persistent cache).
COMPILE_SECONDS = "engine_compile_seconds_total"


# ---------------------------------------------------------------------------
# Encoding / packing (canonical home; ``core.aligner`` re-exports).


def encode(seq: Seq) -> np.ndarray:
    if isinstance(seq, str):
        return np.frombuffer(seq.encode("ascii"), dtype=np.uint8).astype(np.int32)
    if isinstance(seq, bytes):
        return np.frombuffer(seq, dtype=np.uint8).astype(np.int32)
    return np.asarray(seq, dtype=np.int32)


def pack_batch(seqs: Sequence[Seq], pad_to: Optional[int] = None,
               multiple: int = 1):
    """-> (codes [B, L] int32, lens [B] int32). Padding value 0 (never read)."""
    enc = [encode(s) for s in seqs]
    lens = np.asarray([len(e) for e in enc], np.int32)
    L = max(1, pad_to if pad_to is not None else int(lens.max(initial=1)))
    L = ((L + multiple - 1) // multiple) * multiple
    out = np.zeros((len(enc), L), np.int32)
    for i, e in enumerate(enc):
        out[i, : len(e)] = e
    return out, lens


# ASCII codes that the kernel's 2-bit fetch packs one-to-one
_ACGT = np.zeros(256, bool)
_ACGT[np.frombuffer(b"ACGT", np.uint8)] = True


def fetch_char_bits(p: np.ndarray, plen: np.ndarray, t: np.ndarray,
                    tlen: np.ndarray) -> int:
    """Bits per character of the kernel's packed extend fetch for a batch.

    Only codes inside each row's length count: 2 when every one is an
    uppercase A, C, G or T (16 characters compared per trip); else 8 when
    every one lies in 0..255, as everything ``encode`` makes from ``str``
    or ``bytes`` does (4 per trip); else 32 (1 per trip).
    """
    acgt = True
    for codes, lens in ((p, plen), (t, tlen)):
        codes = np.asarray(codes)
        lens = np.asarray(lens).reshape(-1)
        if codes.size == 0:
            continue
        if codes.min() < 0 or codes.max() > 255:
            # out of byte range somewhere: only the padding may be
            inside = np.arange(codes.shape[1]) < lens[:, None]
            vals = codes[inside]
            if vals.size and (vals.min() < 0 or vals.max() > 255):
                return 32
            codes = np.where(inside, codes, ord("A"))
        if acgt:
            ok = _ACGT.take(codes.astype(np.uint8))
            # each row's first code outside ACGT lies past its length
            acgt = bool((ok.all(axis=1) | (ok.argmin(axis=1) >= lens)).all())
    return 2 if acgt else 8


def problem_bounds(pen, plens: np.ndarray, tlens: np.ndarray,
                   edit_frac: Optional[float], s_max: Optional[int] = None,
                   k_max: Optional[int] = None) -> Tuple[int, int]:
    """Static (s_max, k_max) for a batch (``pen``: model or legacy triple).

    With ``edit_frac`` (the paper's E): the model's score bound over the
    batch max length.  Without it: the exact worst case (all-mismatch
    diagonal + one gap), which guarantees every pair terminates with a
    real score.
    """
    pen = scoring.as_model(pen)
    max_len = int(max(plens.max(initial=1), tlens.max(initial=1)))
    max_diff = int(np.abs(tlens - plens).max(initial=0))
    if s_max is None:
        if edit_frac is not None:
            s_max = pen.score_bound(max_len, edit_frac, len_diff=max_diff)
        else:
            s_max = _exact_worst_score(pen, plens, tlens)
    if k_max is None:
        k_max = min(pen.band_bound(s_max), max_len)
    k_max = max(k_max, max_diff, 1)
    return int(s_max), int(k_max)


def _exact_worst_score(pen, plens, tlens) -> int:
    """Batch-vectorized :meth:`scoring.PenaltyModel.worst_score`, maxed
    over the batch — the bound under which every pair terminates."""
    worst = (pen.x * np.minimum(plens, tlens)
             + np.where(plens != tlens,
                        pen.o + pen.e * np.abs(tlens - plens), 0))
    return int(worst.max(initial=0)) + 1


def pair_sharding(mesh: Optional[Mesh]) -> Optional[NamedSharding]:
    """Pair axis over ALL mesh axes — every chip is a 'DPU'."""
    if mesh is None:
        return None
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def _next_pow2(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


def _round_up(v: int, m: int) -> int:
    return ((v + m - 1) // m) * m


def _quantize_rows(n: int, multiple: int) -> int:
    """Smallest 'round' pair count >= n — a power of two or 1.5x one
    (bounds padding waste at 25% while keeping the set of distinct batch
    shapes, and so the executable cache, small) — then rounded up to
    ``multiple`` (the worker count)."""
    p = _next_pow2(n)
    if p > 1 and 3 * p // 4 >= n:
        p = 3 * p // 4
    return _round_up(p, multiple)


def _fit_width(arr: np.ndarray, width: int) -> np.ndarray:
    """Pad or trim the column axis to ``width`` (padding never read)."""
    if arr.shape[1] == width:
        return arr
    if arr.shape[1] > width:
        return arr[:, :width]
    out = np.zeros((arr.shape[0], width), arr.dtype)
    out[:, : arr.shape[1]] = arr
    return out


def _pad_rows(arr: np.ndarray, to: int) -> np.ndarray:
    if arr.shape[0] == to:
        return arr
    pad = np.zeros((to - arr.shape[0],) + arr.shape[1:], arr.dtype)
    return np.concatenate([arr, pad], axis=0)


# ---------------------------------------------------------------------------
# Stats / results.


@dataclasses.dataclass
class PIMStats:
    """Phase accounting of the paper's host<->device pipeline (Fig. 1)."""
    n_pairs: int
    n_workers: int
    bytes_in: int
    bytes_out: int
    t_scatter: float
    t_kernel: float
    t_gather: float

    @property
    def t_total(self) -> float:
        return self.t_scatter + self.t_kernel + self.t_gather

    def throughput_total(self) -> float:
        return self.n_pairs / max(self.t_total, 1e-12)

    def throughput_kernel(self) -> float:
        return self.n_pairs / max(self.t_kernel, 1e-12)


@dataclasses.dataclass(frozen=True)
class BucketInfo:
    """One executed problem shape: quantized length + static WFA bounds."""
    lmax: int
    s_max: int
    k_max: int
    n_pairs: int
    recovery: bool = False     # True for adaptive second-pass buckets


@dataclasses.dataclass
class EngineStats:
    """Telemetry for one ``align`` call."""
    n_pairs: int = 0
    n_workers: int = 1
    buckets: List[BucketInfo] = dataclasses.field(default_factory=list)
    n_overflow: int = 0        # pairs unresolved after pass 1
    n_recovered: int = 0       # of those, resolved by the exact-bound pass
    cache_hits: int = 0
    cache_misses: int = 0
    n_traces: int = 0          # fresh XLA traces triggered by this call
    n_ext_trips: int = 0       # Pallas-kernel extend trips, summed over
                               # grid blocks (0 on backends without them)
    rows_real: int = 0         # submitted rows actually dispatched in waves
    rows_padded: int = 0       # device rows incl. quantization padding
    bytes_in: int = 0
    bytes_out: int = 0
    t_scatter: float = 0.0
    t_kernel: float = 0.0
    t_gather: float = 0.0
    # BiWFA (trace_variant="bidir") telemetry
    n_meet_unmet: int = 0      # meet rows whose fronts never joined
    n_bidir_fallback: int = 0  # segments re-run via packed traceback
    peak_trace_bytes: int = 0  # largest trace buffer gathered for one wave
                               # (the resident trace-memory high-water mark)

    def merge(self, other: "EngineStats", *,
              count_pairs: bool = True) -> "EngineStats":
        """Fold ``other``'s telemetry into this one, in place -> self.

        Additive fields sum, ``buckets`` extend, high-water marks max.
        ``count_pairs=False`` skips ``n_pairs`` — for aggregating child
        tickets (BiWFA sub-problems, mapper extension rounds) whose rows
        re-process pairs the parent already counted.
        """
        if count_pairs:
            self.n_pairs += other.n_pairs
        self.n_workers = max(self.n_workers, other.n_workers)
        self.buckets.extend(other.buckets)
        for f in ("n_overflow", "n_recovered", "cache_hits", "cache_misses",
                  "n_traces", "n_ext_trips", "rows_real", "rows_padded",
                  "bytes_in", "bytes_out", "t_scatter", "t_kernel",
                  "t_gather", "n_meet_unmet", "n_bidir_fallback"):
            setattr(self, f, getattr(self, f) + getattr(other, f))
        self.peak_trace_bytes = max(self.peak_trace_bytes,
                                    other.peak_trace_bytes)
        return self

    @property
    def n_buckets(self) -> int:
        return len([b for b in self.buckets if not b.recovery])

    @property
    def wave_occupancy(self) -> float:
        """Real rows / device rows across every dispatched wave (1.0 when
        nothing has been dispatched): how much of the padded rectangles the
        executable cache's quantized shapes actually carried."""
        return (self.rows_real / self.rows_padded if self.rows_padded
                else 1.0)

    @property
    def padding_waste_frac(self) -> float:
        """Fraction of dispatched device rows that were quantization
        padding — the batching-efficiency complement of
        :attr:`wave_occupancy`."""
        return 1.0 - self.wave_occupancy

    @property
    def pim(self) -> PIMStats:
        return PIMStats(n_pairs=self.n_pairs, n_workers=self.n_workers,
                        bytes_in=self.bytes_in, bytes_out=self.bytes_out,
                        t_scatter=self.t_scatter, t_kernel=self.t_kernel,
                        t_gather=self.t_gather)


@dataclasses.dataclass
class EngineResult:
    scores: np.ndarray                      # [B] int32; -1 = exceeded s_max
    cigars: Optional[List[np.ndarray]]      # per-pair op arrays, or None
    n_steps: int                            # score-loop steps of the
                                            # gcd-reduced model (telemetry)
    s_max: int                              # largest bound used
    k_max: int
    stats: EngineStats = dataclasses.field(default_factory=EngineStats)
    # True when a non-exact wavefront heuristic produced these results:
    # scores are an upper bound on the optimal cost and divergent pairs may
    # stay unresolved (-1).
    approximate: bool = False

    def cigar_strings(self, mode: str = "extended") -> List[str]:
        """Run-length CIGAR strings (``mode``: SAM 1.4 'extended' ``=``/``X``
        or 'classic' ``M``)."""
        if self.cigars is None:
            raise ValueError("no CIGARs: align with output='cigar'")
        return [cigar_mod.cigar_string(c, mode) for c in self.cigars]

    def cigar_identities(self) -> np.ndarray:
        """[B] float fraction of matching alignment columns per pair.

        Unresolved pairs (``score == -1``: no alignment was produced) are
        NaN, not 1.0 — an empty op array only means "identical" when the
        pair actually resolved (both sequences empty).
        """
        if self.cigars is None:
            raise ValueError("no CIGARs: align with output='cigar'")
        return np.asarray([
            cigar_mod.cigar_identity(c) if s >= 0 else np.nan
            for s, c in zip(self.scores, self.cigars)])


class _Executable:
    """One compiled backend entry point for a fixed problem shape.

    Tracing happens at most once per (shape, bounds) key; ``n_traces``
    counts actual XLA traces so callers can assert cache effectiveness.
    ``call`` is the dispatch point shared by the sync path and the
    streaming session: it honors the backend's ``dispatch`` hook and is
    *non-blocking* — the returned ``WFAResult`` holds in-flight device
    arrays (JAX async dispatch), so callers choose when to synchronize.

    The backend runs ``run_pen``, the caller's model divided by the gcd
    ``score_scale`` of its penalties (:meth:`~repro.core.scoring.
    PenaltyModel.reduced`), with the bound ``s_max // score_scale``: a
    pair of cost S <= ``s_max`` resolves to S / ``score_scale``, and one
    above ``s_max`` stays unresolved, so the same pairs come back ``-1``
    as under the caller's model.  Its result is in the reduced units: the
    session multiplies scores back and decodes traces against
    ``run_pen``.  ``s_max`` and ``k_max`` stay the caller's.  Breakpoint
    waves (``"bidir_meet"``), boundary-state children and bounds below
    the gcd run the caller's model (``score_scale`` 1): their known costs
    and caps are the BiWFA recursion's, in the caller's units.
    """

    def __init__(self, spec: BackendSpec, pen, s_max: int,
                 k_max: int, mesh: Optional[Mesh], output: str = "score",
                 heur=None, states: Tuple[str, str] = ("M", "M"),
                 opts: Tuple[Tuple[str, object], ...] = (),
                 char_bits: int = 32):
        self.s_max = s_max
        self.k_max = k_max
        self.compiled = False     # set by the first call
        self._traces = [0]
        traces = self._traces
        pen = scoring.as_model(pen)
        heur = scoring.as_heuristic(heur)
        states = tuple(states)
        run_pen, scale = pen, 1
        if output != "bidir_meet" and states == ("M", "M"):
            run_pen, scale = pen.reduced()
            if s_max < scale:
                run_pen, scale = pen, 1
        self.run_pen = run_pen
        self.score_scale = scale
        run_s_max = s_max // scale
        if output == "bidir_meet":
            # the meet-in-the-middle breakpoint solver: the jnp solver
            # serves every backend
            backend_fn = wf.wfa_bidir_meet
            self._dispatch = None
            extra = {}
        else:
            backend_fn = spec.variant(output, pen.kind)
            self._dispatch = spec.dispatch
            extra = {"mesh": mesh} if spec.needs_mesh else {}
        # Backend tuning opts: ``band_cap="auto"`` resolves through the
        # heuristic's own cap for this problem's band width (exact
        # alignment has no pruning radius, so "auto" stays full-width).
        # Each opt is then threaded only into callables whose signature
        # takes it — the stateful-children ring substitution and the meet
        # solver keep working with kernel-only knobs configured.
        opts = dict(opts)
        if opts.get("band_cap") == "auto":
            opts["band_cap"] = (None if heur.exact
                                else heur.band_cap(2 * k_max + 1))
        for kw, val in opts.items():
            if val is not None and _accepts_kw(backend_fn, kw):
                extra[kw] = val
        # characters compared per extend trip, for backends with a packed
        # fetch (None: the backend has none)
        self.chars_per_trip = None
        if _accepts_kw(backend_fn, "char_bits"):
            extra["char_bits"] = char_bits
            self.chars_per_trip = 32 // char_bits
        # Only pass heur when pruning is actually requested, so
        # heuristic-unaware plug-in backends keep serving exact alignment.
        if not heur.exact:
            if output != "bidir_meet" and not spec.accepts_heuristic(output):
                raise ValueError(
                    f"backend {spec.name!r} does not accept wavefront "
                    f"heuristics (no 'heur' keyword on its "
                    f"{output}-variant); use heuristic=None or a "
                    f"heuristic-aware backend")
            extra["heur"] = heur
        if states != ("M", "M"):
            # boundary-constrained sub-alignment (BiWFA recursion child);
            # the engine substitutes a state-capable trace path upstream
            extra["begin_state"], extra["end_state"] = states

        def _run(*arrays):
            traces[0] += 1            # trace-time side effect only
            return backend_fn(*arrays, pen=run_pen,
                              s_max=run_s_max, k_max=k_max, **extra)

        # Donation is a no-op (with a warning) on CPU; only apply it where
        # XLA can actually alias the buffers.
        donate = (spec.donate_args
                  if output != "bidir_meet"
                  and jax.default_backend() in ("gpu", "tpu") else ())
        self.fn = jax.jit(_run, donate_argnums=donate)

    def call(self, *arrays):
        if self.compiled:
            return self._call(arrays)
        # the first call traces and compiles (or reads JAX's persistent
        # cache) before it dispatches: its host seconds are set-up
        t0 = time.perf_counter()
        res = self._call(arrays)
        self.compiled = True
        obs_metrics.counter(COMPILE_SECONDS,
                            "host seconds in executables' first calls "
                            "(trace + compile or persistent-cache read)"
                            ).inc(time.perf_counter() - t0)
        return res

    def _call(self, arrays):
        if self._dispatch is not None:
            return self._dispatch(self.fn, *arrays)
        return self.fn(*arrays)

    @property
    def n_traces(self) -> int:
        return self._traces[0]


class AlignmentEngine:
    """Bucketed, cached, overflow-recovering batch aligner.

    Parameters
    ----------
    pen : default penalty model — any :class:`~repro.core.scoring.
        PenaltyModel` (``Edit`` / ``GapLinear`` / ``GapAffine``) or a
        legacy gap-affine :class:`Penalties` triple (normalized to
        ``GapAffine``).  Every ``align``/``submit`` can override per call
        via ``penalties=``; linear models run the cheaper one-matrix
        recurrence end to end.  Executables run the model divided by the
        gcd of its penalties (4/6/2 runs as 2/3/1, with half the score
        steps); scores, bounds, stats and CIGARs come back in this
        model's units.
    backend : registry name (``available_backends()``); plug-ins welcome.
    edit_frac : the paper's E — optimistic score budget for pass 1.  ``None``
        sizes buffers for the exact worst case up front (single pass).
    s_max / k_max : explicit static bounds; setting ``s_max`` pins the score
        cap (no adaptive recovery — unresolved pairs stay ``-1``).
    output : default output mode for calls that don't name one —
        ``"score"`` (throughput) or ``"cigar"`` (full alignments via the
        backend's trace variant).  Every ``align``/``submit`` can override
        per call.
    heuristic : default :class:`~repro.core.scoring.WavefrontHeuristic`
        (``None`` = exact).  ``AdaptiveBand``/``ZDrop`` prune wavefront
        lanes per score step; results are flagged ``approximate=True``.
        Per-call ``heuristic=`` overrides.
    with_cigar : deprecated spelling of ``output="cigar"`` (kept for
        compatibility; per-call ``output=`` is the API).
    mesh : device mesh for scatter/gather (and for ``needs_mesh`` backends,
        which span every local device on a 1-D ``("pairs",)`` mesh when
        given none).
    chunk_pairs : max pairs per device wave (the MRAM-capacity analogue).
    bucket_by_length : sort pairs into power-of-two length buckets.
    min_bucket_len : floor for bucket lengths (avoids tiny-shape churn).
    adaptive : enable the exact-bound recovery pass for overflow pairs.
    backend_opts : backend tuning knobs, threaded by keyword into each of
        the backend's callables that takes them.  Built-ins:
        ``band_cap`` (compacting-band ring width on ring/kernel/shardmap;
        ``"auto"`` derives it from the active heuristic's pruning radius
        via ``heur.band_cap`` — exact alignment stays full-width), plus
        ``block_pairs`` on ``kernel`` / ``shardmap``.  Unknown keys raise
        ``ValueError`` here, not at align time, and so does ``char_bits``:
        the kernel's fetch width follows each ticket's codes
        (:func:`fetch_char_bits`).  The kernel has one fetch and the
        BiWFA meet waves one solver, so neither is an option.
    """

    def __init__(self, pen=DEFAULT, *, backend: str = "ring",
                 edit_frac: Optional[float] = None,
                 s_max: Optional[int] = None, k_max: Optional[int] = None,
                 output: str = "score", heuristic=None,
                 with_cigar: bool = False,
                 mesh: Optional[Mesh] = None,
                 chunk_pairs: int = 1 << 16, bucket_by_length: bool = True,
                 min_bucket_len: int = 16, adaptive: bool = True,
                 trace_variant: str = "packed",
                 max_wave_cells: int = 1 << 24,
                 trace_budget: Optional[int] = None,
                 backend_opts: Optional[Dict[str, object]] = None):
        spec = get_backend(backend)
        self.backend_opts = dict(backend_opts or {})
        if "char_bits" in self.backend_opts:
            raise ValueError("char_bits is not a backend option: it is "
                             "derived from the codes of each submission")
        for kw in sorted(self.backend_opts):
            if not any(_accepts_kw(f, kw) for f in spec.callables()):
                raise ValueError(
                    f"backend {backend!r} accepts no backend_opts key "
                    f"{kw!r} on any of its callables")
        if with_cigar:
            output = "cigar"
        if output not in ("score", "cigar"):
            raise ValueError(f"unknown output mode {output!r}; "
                             "use 'score' or 'cigar'")
        if trace_variant not in ("packed", "bidir"):
            raise ValueError(f"unknown trace variant {trace_variant!r}; "
                             "use 'packed' or 'bidir'")
        if output == "cigar" and not spec.supports_cigar:
            raise ValueError(
                f"CIGAR output needs a backend with a trace variant; "
                f"{backend!r} is score-only")
        if spec.needs_mesh and mesh is None:
            # a sharding backend given no mesh splits every wave over all
            # of this host's devices
            mesh = make_mesh((jax.local_device_count(),), ("pairs",),
                             devices=jax.local_devices())
        self.pen = scoring.as_model(pen)
        spec.variant("score", self.pen.kind)   # raises if model unsupported
        self.heuristic = scoring.as_heuristic(heuristic)
        self.backend = backend
        self.edit_frac = edit_frac
        self._s_max = s_max
        self._k_max = k_max
        self.default_output = output
        self.mesh = mesh
        self.chunk_pairs = int(chunk_pairs)
        self.bucket_by_length = bucket_by_length
        self.min_bucket_len = int(min_bucket_len)
        self.adaptive = adaptive
        self.trace_variant = trace_variant
        # long-read bucket ladder: cap rows-per-wave so wide buckets (100 kb
        # pairs) dispatch narrow waves instead of OOMing at chunk_pairs rows
        self.max_wave_cells = int(max_wave_cells)
        # bidir recursion base case: packed traceback allowed when a
        # sub-problem's s*(plen+tlen) fits this many cells (None = default)
        self.trace_budget = trace_budget
        # waves pad to a multiple of the devices they are split over: the
        # mesh's, or the one default device a mesh-less engine places on
        self.n_workers = (int(np.prod(list(mesh.shape.values())))
                          if mesh is not None else 1)
        self._cache: Dict[tuple, _Executable] = {}

    @property
    def with_cigar(self) -> bool:
        """Deprecated: whether the *default* output mode emits CIGARs."""
        return self.default_output == "cigar"

    def resolve_output(self, output: Optional[str], pen=None) -> str:
        """Validate a per-call output mode (None -> the engine default).

        ``pen`` is the call's resolved penalty model (None -> the engine
        default): the cigar check must name the model kind actually in
        play, or a linear-only backend would be rejected for 'affine'.
        """
        out = self.default_output if output is None else output
        if out not in ("score", "cigar"):
            raise ValueError(f"unknown output mode {output!r}; "
                             "use 'score' or 'cigar'")
        if out == "cigar":
            kind = (self.pen if pen is None else pen).kind
            get_backend(self.backend).variant("cigar", kind)
        return out

    def resolve_trace_variant(self, trace_variant: Optional[str],
                              output: str = "score") -> str:
        """Validate a per-call trace variant (None -> the engine default).

        ``"bidir"`` selects the meet-in-the-middle BiWFA traceback
        (``repro.biwfa``) for CIGAR submissions: O(s) trace memory instead
        of the packed O(s^2) backtrace.  It only changes how CIGARs are
        produced, so score-only submissions normalize to ``"packed"``.
        """
        tv = self.trace_variant if trace_variant is None else trace_variant
        if tv not in ("packed", "bidir"):
            raise ValueError(f"unknown trace variant {trace_variant!r}; "
                             "use 'packed' or 'bidir'")
        return tv if output == "cigar" else "packed"

    def resolve_penalties(self, pen) -> "scoring.PenaltyModel":
        """Validate a per-call penalty model (None -> the engine default)."""
        model = self.pen if pen is None else scoring.as_model(pen)
        get_backend(self.backend).variant("score", model.kind)
        return model

    def resolve_heuristic(self, heur,
                          output: str = "score") -> "scoring.WavefrontHeuristic":
        """Validate a per-call heuristic (None -> the engine default).

        The backend-capability check happens here — before any ticket is
        created — so a rejected submit leaves the session clean instead of
        registering a ticket whose waves can never dispatch.
        """
        heur = self.heuristic if heur is None else scoring.as_heuristic(heur)
        if not heur.exact:
            spec = get_backend(self.backend)
            if not spec.accepts_heuristic(output):
                raise ValueError(
                    f"backend {self.backend!r} does not accept wavefront "
                    f"heuristics (no 'heur' keyword on its "
                    f"{output}-variant); use heuristic=None or a "
                    f"heuristic-aware backend")
        return heur

    # -- cache introspection -------------------------------------------------

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    def cache_traces(self) -> int:
        """Total XLA traces across all cached executables."""
        return sum(e.n_traces for e in self._cache.values())

    # -- bounds --------------------------------------------------------------

    def _bounds_for_bucket(self, lmax: int, plen_b: np.ndarray,
                           tlen_b: np.ndarray, exact: bool,
                           pen=None, s_cap: Optional[int] = None
                           ) -> Tuple[int, int]:
        """Static (s_max, k_max) for one bucket.

        Pass-1 bounds depend only on (pen, lmax, edit_frac) — never on the
        data — so identical buckets across calls share one executable.  The
        exact path quantizes s_max up to a multiple of 32 for the same
        reason (the score loop exits early regardless).  ``pen`` is the
        per-call penalty model (None -> the engine default): cheaper models
        imply tighter E-derived score bounds (edit distance: ``s_max``
        close to the edit budget itself), so the score loop cap shrinks
        with the model.

        ``s_cap`` is a per-submit score ceiling: the BiWFA recursion
        dispatches sub-problems whose cost is already known, so their waves
        run far below the bucket's worst case (callers quantize the cap for
        cache reuse).
        """
        pen = self.pen if pen is None else pen
        max_diff = int(np.abs(tlen_b - plen_b).max(initial=0))
        if self._s_max is not None:
            s = int(self._s_max)
        elif not exact and self.edit_frac is not None:
            # regime bound: at most ceil(E*L) edits, so the length diff is
            # at most that many bases too — fully data-independent (no
            # max_diff bump: the band provably covers any within-budget
            # pair, and over-budget pairs go to the recovery pass anyway)
            n_err = int(math.ceil(self.edit_frac * lmax))
            s = int(pen.score_bound(lmax, self.edit_frac, len_diff=n_err))
            max_diff = 0
        else:
            s = _round_up(_exact_worst_score(pen, plen_b, tlen_b), 32)
        if s_cap is not None:
            s = max(min(s, int(s_cap)), 1)
        k = self._k_max if self._k_max is not None else \
            min(pen.band_bound(s), lmax)
        return int(s), max(int(k), max_diff, 1)

    # -- bucket planning -----------------------------------------------------

    def _plan_buckets(self, plen: np.ndarray, tlen: np.ndarray,
                      idx: np.ndarray) -> List[Tuple[int, np.ndarray]]:
        """-> [(bucket_len, original-row indices)] sorted by length."""
        lmax = np.maximum(plen[idx], tlen[idx])
        if not self.bucket_by_length:
            width = _next_pow2(max(int(lmax.max(initial=1)),
                                   self.min_bucket_len))
            return [(width, idx)]
        widths = np.maximum(lmax, self.min_bucket_len)
        widths = 2 ** np.ceil(np.log2(np.maximum(widths, 1))).astype(np.int64)
        out = []
        for w in np.unique(widths):
            out.append((int(w), idx[widths == w]))
        return out

    # -- execution -----------------------------------------------------------

    def _device_put(self, *arrays):
        sh = pair_sharding(self.mesh)
        if sh is not None:
            return tuple(jax.device_put(a, sh) for a in arrays)
        return tuple(jnp.asarray(a) for a in arrays)

    def _executable_for(self, pshape: tuple, tshape: tuple, s_max: int,
                        k_max: int, output: str = "score",
                        pen=None, heur=None,
                        states: Tuple[str, str] = ("M", "M"),
                        char_bits: int = 32
                        ) -> Tuple["_Executable", bool]:
        """Cached executable for one rectangular problem shape -> (exe, hit).

        ``char_bits`` is the ticket's fetch class (:func:`fetch_char_bits`);
        it keys the cache only where the backend's callable takes it."""
        spec = get_backend(self.backend)
        states = tuple(states)
        if output == "cigar" and states != ("M", "M") \
                and not spec.accepts_states():
            # boundary-constrained children (BiWFA recursion) need a
            # state-aware trace path; fall back to the ring solver for
            # backends whose trace variant can't seed mid-gap fronts
            spec = get_backend("ring")
        pen = self.pen if pen is None else pen
        heur = self.heuristic if heur is None else heur
        # the whole spec in the key: re-registering a backend name (new fn,
        # donation or dispatch hooks) must not serve stale executables.
        # output mode, penalty model, heuristic, boundary states and
        # backend opts too: each compiles a different recurrence /
        # pruning / seeding / blocking step.
        opts = tuple(sorted(self.backend_opts.items()))
        if output == "bidir_meet" or not _accepts_kw(
                spec.variant(output, pen.kind), "char_bits"):
            char_bits = 32
        key = (spec, pen, heur, pshape, tshape, s_max, k_max, output, states,
               opts, char_bits)
        exe = self._cache.get(key)
        if exe is not None:
            obs_metrics.counter("engine_cache_hits_total",
                                "executable cache hits").inc()
            return exe, True
        obs_metrics.counter("engine_cache_misses_total",
                            "executable cache misses (fresh XLA trace "
                            "on first call)").inc()
        if obs_trace.enabled():
            obs_trace.instant("engine.retrace", args={
                "backend": spec.name, "shape": list(pshape),
                "s_max": s_max, "k_max": k_max, "output": output})
        exe = _Executable(spec, pen, s_max, k_max, self.mesh, output, heur,
                          states, opts, char_bits)
        self._cache[key] = exe
        return exe, False

    # -- public entry points -------------------------------------------------

    def stream(self, *, max_inflight_waves: int = 2,
               wave_pairs: Optional[int] = None):
        """Open a pipelined :class:`~repro.core.session.AlignmentSession`.

        The session is the canonical submission path: ``submit()`` returns a
        :class:`~repro.core.session.Ticket` immediately, host-side packing of
        the next wave overlaps the in-flight device kernel (JAX async
        dispatch), at most ``max_inflight_waves`` waves are in flight
        (backpressure), and tickets complete out of order via
        ``as_completed()``.  ``wave_pairs`` defaults to the engine's
        ``chunk_pairs`` (the MRAM-capacity analogue).
        """
        from repro.core.session import AlignmentSession
        return AlignmentSession(self, max_inflight_waves=max_inflight_waves,
                                wave_pairs=wave_pairs)

    def align(self, patterns: Sequence[Seq], texts: Sequence[Seq], *,
              output: Optional[str] = None, penalties=None,
              heuristic=None, trace_variant: Optional[str] = None
              ) -> EngineResult:
        """Align python sequences (str/bytes/int arrays), pairwise.

        ``output="cigar"`` additionally emits exact per-pair CIGAR op
        arrays (``EngineResult.cigars``) via the backend's trace variant;
        ``penalties=`` selects a per-call penalty model and ``heuristic=``
        a per-call wavefront heuristic; ``trace_variant="bidir"`` produces
        the CIGARs through the O(s)-memory BiWFA recursion instead of the
        packed backtrace; ``None`` uses the engine defaults.
        """
        assert len(patterns) == len(texts)
        p, plen = pack_batch(patterns)
        t, tlen = pack_batch(texts)
        return self.align_packed(p, plen, t, tlen, output=output,
                                 penalties=penalties, heuristic=heuristic,
                                 trace_variant=trace_variant)

    def align_packed(self, p: np.ndarray, plen: np.ndarray, t: np.ndarray,
                     tlen: np.ndarray, *, output: Optional[str] = None,
                     penalties=None, heuristic=None,
                     trace_variant: Optional[str] = None) -> EngineResult:
        """Align pre-packed rectangular batches ([B, L] codes + [B] lens).

        Thin blocking wrapper over one streaming session: a single
        ``submit`` followed by ``drain``, with per-phase (scatter / kernel /
        gather) blocking so the Fig. 1 decomposition stays measurable.
        """
        from repro.core.session import AlignmentSession
        sess = AlignmentSession(self, max_inflight_waves=1,
                                _sync_timing=True)
        ticket = sess.submit_packed(p, plen, t, tlen, output=output,
                                    penalties=penalties,
                                    heuristic=heuristic,
                                    trace_variant=trace_variant)
        sess.drain()
        return ticket.result()

    def align_pair(self, pattern: Seq, text: Seq, *,
                   output: Optional[str] = None, penalties=None,
                   heuristic=None, trace_variant: Optional[str] = None
                   ) -> EngineResult:
        return self.align([pattern], [text], output=output,
                          penalties=penalties, heuristic=heuristic,
                          trace_variant=trace_variant)
