"""Measure the chip's elementwise int32 vector rate, for the peaks table.

    python3 chipbench/vector_rate.py

The TPU's int32 vector rate is not published, so it is measured once
with this microkernel and written into ``peaks.json`` as a constant; the
benchmark never measures it again.  The kernel keeps an int32 tile
resident in VMEM and applies ``a = max(a + c, d)`` to every element for
``n_iter`` iterations: two elementwise int32 operations per element and
iteration, with no HBM traffic inside the loop.  Each tile row is an
independent dependence chain, so the vector units see enough independent
work.  The rate is the best over a few tile heights of
``2 * rows * 128 * n_iter`` operations over the kernel's device time
(host clock around ``block_until_ready``, best of several repeats, with
``n_iter`` sized so one call lasts about half a second).
"""
from __future__ import annotations

import json
import sys
import time

LANES = 128
UNROLL = 8


def _rate_for(rows: int, n_iter: int, repeats: int = 5) -> float:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        c = x_ref[...]
        d = c ^ 0x5A5A

        def body(_, a):
            for _ in range(UNROLL):
                a = jnp.maximum(a + c, d)
            return a

        o_ref[...] = lax.fori_loop(0, n_iter // UNROLL, body, c)

    call = jax.jit(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.int32)))
    x = jnp.arange(rows * LANES, dtype=jnp.int32).reshape(rows, LANES)
    call(x).block_until_ready()
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        call(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return 2.0 * rows * LANES * n_iter / best, best


def measure() -> dict:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX runs on {dev.platform!r}")
    out = {"device_kind": dev.device_kind, "runs": []}
    for rows in (64, 128, 256, 512):
        n_iter = 4096
        rate, secs = _rate_for(rows, n_iter)
        # size the loop so one call lasts about half a second
        scale = 0.5 / max(secs, 1e-6)
        n_iter = max(n_iter, int(n_iter * scale) // UNROLL * UNROLL)
        rate, secs = _rate_for(rows, n_iter)
        out["runs"].append({"rows": rows, "n_iter": n_iter,
                            "seconds": secs, "int32_ops_per_s": rate})
        print(f"[vector_rate] rows={rows} n_iter={n_iter} "
              f"{secs:.4f}s {rate:.4e} int32 ops/s", file=sys.stderr)
    out["int32_ops_per_s"] = max(r["int32_ops_per_s"] for r in out["runs"])
    return out


if __name__ == "__main__":
    print(json.dumps(measure()))
