"""Quickstart: the unified AlignmentEngine API.

One object covers every alignment scenario:

* ``AlignmentEngine(backend=...)`` picks an execution strategy from the
  backend registry — ``"ref"`` (pure-jnp reference), ``"ring"``
  (rolling-window throughput), ``"kernel"`` (Pallas TPU kernel),
  ``"shardmap"`` (the Pallas kernel per shard over every device) — and
  plug-ins can ``register_backend`` their own without touching core code.
* Every call picks an output mode: ``output="score"`` (default) or
  ``output="cigar"`` — full alignments on *any* built-in backend, via the
  packed 2-bit backtrace (``ring``/``kernel``/``shardmap``) or the full
  history (``ref``).
* Mixed-length batches are split into power-of-two length buckets, so short
  pairs never pay the longest pair's padded band; compiled executables are
  cached per bucket shape, so serving-time calls re-trace nothing.
* With ``edit_frac`` (the paper's E), buffers are sized optimistically and
  the rare over-budget pair is transparently re-run with exact worst-case
  bounds — every score is real, the common case stays fast.

* ``engine.stream()`` opens an ``AlignmentSession`` — async ``submit()``,
  pipelined dispatch (host packing overlaps the in-flight device kernel),
  out-of-order ``as_completed()`` gather.  The blocking ``align()`` is a
  thin wrapper over the same session.

    PYTHONPATH=src python examples/quickstart.py

(The old ``WFAligner`` / ``PIMBatchAligner`` names still work as deprecated
thin wrappers over the engine.)
"""
import numpy as np

from repro.core import DEFAULT, AlignmentEngine, Penalties, available_backends
from repro.core.gotoh import gotoh_score

print("registered backends:", available_backends())

# -- 1. score + CIGAR for a handful of pairs ------------------------------
# output="cigar" works on every built-in backend: "ring"/"kernel" record a
# packed 2-bit backtrace (~16x smaller than "ref"'s full history)
engine = AlignmentEngine(DEFAULT, backend="ring")
patterns = ["ACGTTAGCCA", "GATTACA", "TTTTTTTT"]
texts = ["ACGTCAGCCA", "GATTTACA", "TTTT"]
res = engine.align(patterns, texts, output="cigar")

print("gap-affine penalties:", DEFAULT)
for p, t, s, c, cc in zip(patterns, texts, res.scores, res.cigar_strings(),
                          res.cigar_strings("classic")):
    print(f"  {p:12s} vs {t:12s} -> cost {s:3d}  cigar {c}  ({cc})")

# -- 2. exactness: WFA == dense Gotoh DP (the paper's correctness contract)
for p, t, s in zip(patterns, texts, res.scores):
    g = gotoh_score(np.frombuffer(p.encode(), np.uint8),
                    np.frombuffer(t.encode(), np.uint8), DEFAULT)
    assert s == g, (p, t, s, g)
print("all scores match the dense DP oracle")

# -- 3. throughput mode: mixed-length batch, bucketed + cached -------------
rng = np.random.default_rng(0)
bases = np.frombuffer(b"ACGT", np.uint8)
refs = ["".join(map(chr, bases[rng.integers(0, 4, int(L))]))
        for L in rng.integers(64, 512, size=1000)]
mates = [r[:10] + ("A" if r[10] != "A" else "C") + r[11:] for r in refs]

fast = AlignmentEngine(DEFAULT, backend="ring", edit_frac=0.04)
res = fast.align(refs, mates)
print(f"batch of {len(refs)}: mean cost {res.scores.mean():.2f} across "
      f"{res.stats.n_buckets} length buckets "
      f"({res.stats.n_overflow} overflow -> {res.stats.n_recovered} recovered)")

res2 = fast.align(refs, mates)   # serving-time call: all executables cached
print(f"second call: {res2.stats.cache_hits} cache hits, "
      f"{res2.stats.n_traces} retraces")

# -- 4. streaming: async submit, pipelined waves, out-of-order gather ------
with fast.stream(max_inflight_waves=4) as sess:
    tickets = [sess.submit(refs[lo:lo + 250], mates[lo:lo + 250])
               for lo in range(0, len(refs), 250)]
    done_order = [t.index for t in sess.as_completed()]
print(f"streamed {sess.stats.n_submits} submits as {sess.stats.n_waves} waves "
      f"(peak {sess.stats.peak_inflight} in flight, "
      f"{sess.stats.n_traces} retraces); completion order {done_order}")
streamed = np.concatenate([t.result().scores for t in tickets])
assert streamed.tolist() == res.scores.tolist()
print("streamed scores identical to the blocking path")

# -- 5. edit distance is just another penalty setting ----------------------
ed = AlignmentEngine(Penalties(x=1, o=0, e=1), backend="ring")
print("edit('kitten','sitting') =", ed.align(["kitten"], ["sitting"]).scores[0])
