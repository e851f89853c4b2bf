"""The paper-regime kernels compile for a TPU v5e (described, not attached).

Each test lowers a kernel variant the engine dispatches at the paper's
widths (2,048 pairs, a 128-wide bucket) with ``interpret=False`` for one
chip of a described ``v5e:2x2`` topology, and checks the Mosaic kernel is
in the compiled program.  Each runs at every packed-fetch width the
session can pick (``char_bits`` 2, 8 and 32: 16, 4 and 1 characters per
extend trip), since each lowers its own field count and shifts.  Nothing runs; the compiler refuses here what it
would refuse on the chip (VMEM, unsupported primitives).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import AlignmentEngine
from repro.core.scoring import AdaptiveBand, Edit, GapAffine, GapLinear
from repro.kernels.wfa import ops as kops

PAIRS, WIDTH = 2048, 128
AFFINE = GapAffine(4, 6, 2)
CHAR_BITS = pytest.mark.parametrize("char_bits", [2, 8, 32])


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip cannot be read back from the
    persistent cache, so keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _bounds(pen, edit_frac, exact):
    """The engine's (s_max, k_max) for a 128-wide bucket of ~100 bp pairs."""
    eng = AlignmentEngine(pen, backend="kernel", edit_frac=edit_frac)
    return eng._bounds_for_bucket(WIDTH, np.array([100, 104]),
                                  np.array([104, 100]), exact, pen=pen)


def _compile(one_chip, fn, **kw):
    spec = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    call = jax.jit(functools.partial(fn, interpret=False, **kw))
    return call.lower(spec((PAIRS, WIDTH)), spec((PAIRS, WIDTH)),
                      spec((PAIRS,)), spec((PAIRS,))).compile().as_text()


@pytest.mark.parametrize("pen,edit_frac,exact,trace", [
    (AFFINE, 0.02, False, False),
    (AFFINE, 0.04, True, False),
    (AFFINE, 0.02, False, True),
    (AFFINE, 0.04, True, True),
    (GapLinear(4, 2), 0.02, False, False),
    (Edit(), 0.02, False, False),
    (Edit(), 0.02, True, True),
], ids=["affine-score-optimistic", "affine-score-exact",
        "affine-trace-optimistic", "affine-trace-exact",
        "linear-score-optimistic", "edit-score-optimistic",
        "edit-trace-exact"])
@CHAR_BITS
def test_paper_regime_kernel_compiles(one_chip, no_persistent_cache, pen,
                                      edit_frac, exact, trace, char_bits):
    s_max, k_max = _bounds(pen, edit_frac, exact)
    fn = kops.wfa_align_trace if trace else kops.wfa_align
    hlo = _compile(one_chip, fn, pen=pen, s_max=s_max, k_max=k_max,
                   char_bits=char_bits)
    assert "tpu_custom_call" in hlo


@CHAR_BITS
def test_compacting_band_kernel_compiles(one_chip, no_persistent_cache,
                                         char_bits):
    heur = AdaptiveBand()
    s_max, k_max = _bounds(AFFINE, 0.04, True)
    cap = heur.band_cap(2 * k_max + 1)
    assert kops._band_lanes(cap, kops._round_up(2 * k_max + 1, 128))
    for fn in (kops.wfa_align, kops.wfa_align_trace):
        hlo = _compile(one_chip, fn, pen=AFFINE, s_max=s_max, k_max=k_max,
                       heur=heur, band_cap=cap, char_bits=char_bits)
        assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("k_max,s_max,trace", [(256, 64, False),
                                                (128, 800, True)],
                         ids=["diagonals", "trace-depth"])
def test_vmem_overflow_is_refused_by_name(one_chip, no_persistent_cache,
                                          k_max, s_max, trace):
    fn = kops.wfa_align_trace if trace else kops.wfa_align
    with pytest.raises(ValueError, match="scoped VMEM"):
        _compile(one_chip, fn, pen=AFFINE, s_max=s_max, k_max=k_max)
