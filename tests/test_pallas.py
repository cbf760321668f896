"""Tests: the Pallas shape-fold kernel, run in the Pallas interpreter
(`interpret=True`, passed explicitly — the kernel never infers it). On a
TPU the same code is compiled by Mosaic; `chip_smoke.py` checks that
build against `shape_match` on the smoke's real tables.

Oracle: ops.shapes.shape_match (whose own oracle is utils.topic.match,
tests/test_shapes.py) — bit-identical uint32 arithmetic means results
must be EQUAL, not close.
"""

import numpy as np

import jax

from emqx_tpu.ops import shapes as S
from emqx_tpu.ops.intern import InternTable, PAD
from emqx_tpu.ops.match import encode_topics


def _build_fixture(rng, n_filters=800, n_topics=257, L=8):
    intern = InternTable()
    patterns = [
        lambda: [f"d{rng.integers(0,80)}", "+",
                 f"n{rng.integers(0,100)}", "#"],
        lambda: [f"a{rng.integers(0,400)}", "+"],
        lambda: [f"e{rng.integers(0,80)}", f"x{rng.integers(0,80)}"],
        lambda: ["+", f"y{rng.integers(0,200)}"],
        lambda: ["$sys", f"s{rng.integers(0,50)}"],
        lambda: ["#"],
    ]
    seen, filters = set(), []
    while len(filters) < n_filters:
        ws = patterns[rng.integers(0, len(patterns))]()
        k = "/".join(ws)
        if k not in seen:
            seen.add(k)
            filters.append(ws)
    F = len(filters)
    words = np.full((F, L), PAD, np.int32)
    lens = np.zeros(F, np.int64)
    for i, ws in enumerate(filters):
        lens[i] = len(ws)
        words[i, :len(ws)] = intern.encode_filter(ws)
    st = S.build_shape_tables(words, lens)
    tpats = [
        lambda: [f"d{rng.integers(0,80)}", "m",
                 f"n{rng.integers(0,100)}", "t"],
        lambda: [f"a{rng.integers(0,400)}", "z"],
        lambda: [f"e{rng.integers(0,80)}", f"x{rng.integers(0,80)}"],
        lambda: ["q", f"y{rng.integers(0,200)}"],
        lambda: ["$sys", f"s{rng.integers(0,50)}"],
    ]
    topics = [tpats[rng.integers(0, len(tpats))]()
              for _ in range(n_topics)]
    t, tl, dol, _ = encode_topics(intern, topics, L)
    return st, t, tl, dol


class TestShapeFoldPallas:
    def test_bit_identical_to_xla(self):
        rng = np.random.default_rng(7)
        st, t, tl, dol = _build_fixture(rng)
        stj = jax.device_put(st)
        r_x = S.shape_match(stj, t, tl, dol)
        r_p = S.shape_match_pallas(stj, t, tl, dol, interpret=True)
        np.testing.assert_array_equal(np.asarray(r_x.matches),
                                      np.asarray(r_p.matches))
        np.testing.assert_array_equal(np.asarray(r_x.counts),
                                      np.asarray(r_p.counts))
        assert int(np.asarray(r_x.counts).sum()) > 0  # non-trivial fixture

    def test_dollar_and_padding_rows(self):
        rng = np.random.default_rng(8)
        st, t, tl, dol = _build_fixture(rng, n_filters=50, n_topics=33)
        # zero-length padding rows must match nothing in both backends
        tl = np.asarray(tl).copy()
        tl[:5] = 0
        stj = jax.device_put(st)
        r_x = S.shape_match(stj, t, tl, dol)
        r_p = S.shape_match_pallas(stj, t, tl, dol, interpret=True)
        assert (np.asarray(r_x.counts)[:5] == 0).all()
        np.testing.assert_array_equal(np.asarray(r_x.matches),
                                      np.asarray(r_p.matches))
