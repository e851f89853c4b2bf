"""Chip smoke test: the aligner's main path, once, on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip mesh path only

One chip runs, in order: the device check; batch alignment through the
``repro.launch.align`` launcher at the paper's regime (100 bp pairs,
gap-affine 4/6/2, 2,048 pairs per wave) with the ``kernel`` backend at
E = 2% and E = 4%, in score and in CIGAR mode, each verified against the
Gotoh oracle; proof that the kernel was compiled for the chip and not
interpreted; ``kernel`` against ``ring`` scores on the same pairs; and the
alignment server (``repro.launch.serve_align``) on the ``kernel`` backend.
``--chips 4`` runs the ``shardmap`` backend over the host's four-device
mesh against a one-device ``ring`` run, and nothing else.

Each phase prints a ``[smoke]`` line and any failure exits non-zero.  The
pairs/s, compile times and cache hits printed on the way are smoke
readings, not benchmark results.  The last line of standard output is one
JSON object naming the device.  There is no CPU fallback: without a TPU the
script exits non-zero before it aligns anything.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

PAPER_ARGS = ["--read-len", "100", "--mode", "stream", "--chunk-pairs",
              "2048"]


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileLog:
    """Counts persistent-cache hits/misses and compile seconds (jax events)."""

    def __init__(self):
        import jax.monitoring as mon
        self.hits = self.misses = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def reading(self) -> str:
        return (f"compile {self.compile_s:.1f}s, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


def phase_device(want_count: int) -> dict:
    import jax
    from repro.launch.runtime import device_info
    info = device_info()
    say(f"device: jax {jax.__version__}, {jax.devices()}, "
        f"kind={info['kind']!r}, count={info['count']}")
    check(info["platform"] == "tpu",
          f"no TPU: JAX runs on {info['platform']!r}")
    check(info["count"] >= want_count,
          f"{want_count} devices wanted, {info['count']} found")
    return info


def _align(args):
    from repro.launch import align
    t0 = time.perf_counter()
    rc, scores = align.run(args)
    wall = time.perf_counter() - t0
    check(rc == 0, f"align {' '.join(args)} returned {rc}")
    check(scores is not None and bool((scores >= 0).all()),
          f"align {' '.join(args)}: unresolved pairs")
    return scores, wall


def phase_batch(pairs: int, verify: int, log: CompileLog) -> None:
    for edit_frac in ("0.02", "0.04"):
        for output in ("score", "cigar"):
            args = (["--backend", "kernel", "--pairs", str(pairs),
                     "--edit-frac", edit_frac, "--output", output,
                     "--verify", str(verify)] + PAPER_ARGS)
            scores, wall = _align(args)
            say(f"batch E={float(edit_frac):.0%} {output}: {len(scores)} "
                f"pairs, {verify} verified against Gotoh; smoke reading: "
                f"launcher wall {wall:.1f}s (data generation, warm-up and "
                f"measured run), {log.reading()}")


def phase_compiled(pairs: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import wfa_paper
    from repro.core.engine import AlignmentEngine
    from repro.data.reads import ReadPairSpec, generate_pairs
    from repro.kernels.wfa.ops import default_interpret

    interpret = default_interpret()
    check(interpret is False, "the kernel would run in interpret mode")
    eng = AlignmentEngine(wfa_paper.pen, backend="kernel",
                          edit_frac=wfa_paper.edit_frac,
                          chunk_pairs=wfa_paper.pairs_per_device)
    P, plen, T, tlen = generate_pairs(ReadPairSpec(
        n_pairs=pairs, read_len=wfa_paper.read_len,
        edit_frac=wfa_paper.edit_frac, seed=3))
    eng.align_packed(P, plen, T, tlen)
    check(eng.cache_size > 0, "the kernel engine cached no executable")
    for key, exe in eng._cache.items():
        pshape, tshape = key[3], key[4]
        shapes = [jax.ShapeDtypeStruct(s, jnp.int32)
                  for s in (pshape, tshape, pshape[:1], tshape[:1])]
        hlo = exe.fn.lower(*shapes).compile().as_text()
        check("tpu_custom_call" in hlo,
              f"no tpu_custom_call in the kernel executable {pshape}")
        say(f"kernel compiled: interpret={interpret}, executable "
            f"{list(pshape)} s_max={exe.s_max} k_max={exe.k_max} holds "
            f"tpu_custom_call")


def phase_agree(pairs: int) -> None:
    common = ["--pairs", str(pairs), "--seed", "7", "--edit-frac", "0.04",
              "--verify", "64"] + PAPER_ARGS
    kern, _ = _align(["--backend", "kernel"] + common)
    ring, _ = _align(["--backend", "ring"] + common)
    n_diff = int((kern != ring).sum())
    check(n_diff == 0, f"kernel and ring disagree on {n_diff} pairs")
    say(f"backends agree: kernel == ring on all {pairs} pairs")


def phase_server(requests: int) -> None:
    from repro.core.gotoh import gotoh_score_vec
    from repro.core.scoring import GapAffine
    from repro.data.reads import ArrivalSpec, generate_trace
    from repro.launch import serve_align

    rc, rep = serve_align.run(
        ["--backend", "kernel", "--requests", str(requests),
         "--pairs-per-request", "8", "--read-len", "100", "--edit-frac",
         "0.02", "--seed", "13"])
    check(rc == 0 and rep is not None, f"serve_align returned {rc}")
    st = rep.stats
    check(rep.n_failed == 0, f"{rep.n_failed} requests failed")
    check(rep.n_ok + rep.n_shed == rep.n_requests == requests,
          "not every request resolved")
    check(st.n_accepted == st.n_completed == rep.n_ok
          and st.n_outstanding == 0,
          f"accepted {st.n_accepted}, completed {st.n_completed}, "
          f"delivered {rep.n_ok}, outstanding {st.n_outstanding}")
    check(st.n_retraces == 0, f"{st.n_retraces} retraces after warm-up")
    payloads, _ = generate_trace(ArrivalSpec(
        n_requests=requests, pairs_per_request=8, read_len=100,
        edit_frac=0.02, seed=13))
    pen = GapAffine(4, 6, 2).as_penalties()
    n_checked = 0
    for (p, plen, t, tlen), res in list(zip(payloads, rep.results))[:32]:
        if res is None:
            continue
        want = [gotoh_score_vec(p[i, :plen[i]], t[i, :tlen[i]], pen)
                for i in range(len(plen))]
        check(list(res.scores) == want, "served scores differ from Gotoh")
        n_checked += len(want)
    check(n_checked > 0, "no served request to check")
    say(f"server: {rep.n_ok}/{requests} accepted requests resolved exactly "
        f"once ({rep.n_shed} shed), 0 retraces, {n_checked} served scores "
        f"equal Gotoh; smoke reading: p99 {rep.percentile_ms(99):.1f} ms, "
        f"{rep.sustained_pairs_per_s:,.0f} pairs/s")


def phase_mesh(pairs: int) -> None:
    import jax
    from repro.configs import wfa_paper
    from repro.core.engine import AlignmentEngine
    from repro.data.reads import ReadPairSpec, generate_pairs
    from repro.launch.mesh import make_host_mesh

    n_dev = jax.device_count()
    wave = str(wfa_paper.pairs_per_device * n_dev)
    common = ["--pairs", str(pairs), "--seed", "11", "--edit-frac", "0.02",
              "--verify", "256", "--read-len", "100", "--mode", "stream",
              "--chunk-pairs", wave]
    sharded, _ = _align(["--backend", "shardmap"] + common)
    single, _ = _align(["--backend", "ring"] + common)
    n_diff = int((sharded != single).sum())
    check(n_diff == 0, f"shardmap and one-device ring disagree on "
                       f"{n_diff} pairs")

    eng = AlignmentEngine(wfa_paper.pen, backend="shardmap",
                          edit_frac=wfa_paper.edit_frac,
                          mesh=make_host_mesh(), chunk_pairs=int(wave))
    P, plen, T, tlen = generate_pairs(ReadPairSpec(
        n_pairs=int(wave), read_len=wfa_paper.read_len,
        edit_frac=wfa_paper.edit_frac, seed=11))
    eng.align_packed(P, plen, T, tlen)
    key, exe = next(iter(eng._cache.items()))
    rows = key[3][0]
    dev = eng._device_put(np.zeros(key[3], np.int32),
                          np.zeros(key[4], np.int32),
                          np.zeros(rows, np.int32), np.zeros(rows, np.int32))
    res = exe.call(*dev)
    res.score.block_until_ready()
    for name, arr in zip(("pattern", "text", "plen", "tlen", "score"),
                         dev + (res.score,)):
        shards = arr.addressable_shards
        devices = {s.device for s in shards}
        check(len(devices) == n_dev,
              f"{name} lives on {len(devices)} of {n_dev} devices")
        check(all(s.data.shape[0] == rows // n_dev for s in shards),
              f"{name} is not split evenly over the devices")
    say(f"mesh: shardmap over {n_dev} devices == one-device ring on all "
        f"{pairs} pairs; inputs and scores of a {rows}-row wave split "
        f"{rows // n_dev} rows per device on all {n_dev} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    from repro.launch.runtime import enable_compile_cache
    cache_dir = enable_compile_cache()
    log = CompileLog()
    t0 = time.perf_counter()
    try:
        device = phase_device(args.chips)
        say(f"compile cache: {cache_dir}")
        if args.chips == 4:
            phase_mesh(pairs=1 << 14)
        else:
            phase_batch(pairs=1 << 18, verify=256, log=log)
            phase_compiled(pairs=2048)
            phase_agree(pairs=1 << 16)
            phase_server(requests=384)
    except SmokeFailure as e:
        say(f"FAILED: {e}")
        return 1
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s; "
        f"{log.reading()}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
