"""The comparison that decides `correct`: every message against the oracle.

Inputs are the publishers' send log and the subscribers' receive log (as
`loadgen.py` writes them) and the configuration's population. Each
number compared is returned beside its limit; `correct` is all of them
within limits. The oracle is the population's closed form,
cross-checked on a seeded sample of the topics sent by brute force over
every filter with `plain.py`.
"""

from __future__ import annotations

import zlib

import numpy as np

from benchmark import plain

WARM_PUB = 0xFFFF


def _rows(pub: dict):
    """Index of the send log by (publisher, sequence): rows sorted so
    that row(p, s) = base[p] + s."""
    order = np.lexsort((pub["seq"], pub["pub"]))
    p_sorted = pub["pub"][order]
    n_pubs = int(p_sorted.max()) + 1 if len(order) else 0
    count = np.bincount(p_sorted, minlength=n_pubs)
    base = np.concatenate([[0], np.cumsum(count)[:-1]]).astype(np.int64)
    seq_sorted = pub["seq"][order]
    if len(order) and not np.array_equal(
            seq_sorted, np.arange(len(order)) - np.repeat(base, count)):
        raise ValueError("send log: a publisher's sequence has a hole")
    return order, base, count


def brute_force(pop, keys, n_sample: int, seed: int) -> int:
    """Closed form vs plain matching over every filter, on a seeded
    sample of the distinct keys sent. Returns the number of topics on
    which they differ."""
    distinct = np.unique(keys)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 7])
    pick = rng.choice(distinct, size=min(n_sample, len(distinct)),
                      replace=False)
    owners: dict = {}
    for c in range(pop.conns):
        for f, _q in pop.subscriptions(c):
            owners.setdefault(f, []).append(c)
    filters = pop.filters()
    split = [f.split("/") for f in filters]
    want = pop.expect(pick)
    bad = 0
    for k, key in enumerate(pick):
        got = [c for fi in plain.matching(pop.topic(int(key)), split)
               for c in owners.get(filters[fi], [])]
        bad += sorted(got) != sorted(int(x) for x in want[k] if x >= 0)
    return bad


def check(pop, pub: dict, sub: dict, seed: int, n_sample: int = 16) -> dict:
    """Returns {"numbers": {name: (value, limit)}, "correct": bool,
    "attempted": n, "failed": n, "info": {...}}. Every comparison is
    exact: the limit is 0."""
    n_subs = pop.conns
    M = len(pub["seq"])
    order, base, count = _rows(pub)
    key_s = pub["key"][order]
    qos_s = pub["qos"][order]
    bad_msg = np.zeros(M, bool)

    # ---- deliveries -> messages (the set-up's direct device warm has
    # a publisher id of its own and no send log: left out)
    live = ~sub["dup"] & (sub["pub"] != WARM_PUB)
    s_sub = sub["sub"][live].astype(np.int64)
    s_pub = sub["pub"][live].astype(np.int64)
    s_seq = sub["seq"][live]
    known = (s_pub < len(count)) & (s_seq >= 0)
    known[known] &= s_seq[known] < count[s_pub[known]]
    stray = int((~known).sum())
    m = base[s_pub[known]] + s_seq[known]
    d_sub = s_sub[known]

    # ---- topic and payload of each delivery are the message's own
    uk, inv = np.unique(key_s, return_inverse=True)
    crc_s = np.array([zlib.crc32(pop.topic(int(k)).encode()) for k in uk],
                     np.uint32)[inv]
    d_crc = sub["crc"][live][known]
    topic_bad = d_crc != crc_s[m]
    pay_bad = sub["due_ns"][live][known] != pub["due_ns"][order][m]
    np.logical_or.at(bad_msg, m[topic_bad | pay_bad], True)

    # ---- delivery sets
    got = np.bincount(m * n_subs + d_sub, minlength=M * n_subs) \
        .reshape(M, n_subs).astype(np.int32)
    want = pop.expect(key_s)
    rows = np.repeat(np.arange(M), want.shape[1])
    wp = want.ravel()
    has = wp >= 0
    exp = np.bincount(rows[has] * n_subs + wp[has],
                      minlength=M * n_subs).reshape(M, n_subs)
    wrong = (got != exp).any(axis=1)
    bad_msg |= wrong

    # ---- QoS 1: every PUBLISH acknowledged
    ack_s = pub["ack_ns"][order]
    unacked = (qos_s == 1) & (ack_s == 0)
    bad_msg |= unacked

    # ---- order: per (subscriber, publisher, topic, qos) sequences grow
    d_qos = sub["qos"][live][known].astype(np.int64)
    d_seq = s_seq[known]
    arrival = np.arange(len(m))
    o = np.lexsort((arrival, d_qos, d_crc, s_pub[known], d_sub))
    same = (np.diff(d_sub[o]) == 0) & (np.diff(s_pub[known][o]) == 0) \
        & (np.diff(d_crc[o].astype(np.int64)) == 0) \
        & (np.diff(d_qos[o]) == 0)
    # an equal sequence is the same message again (a second matching
    # subscription of that connection); the set comparison judges it
    breaks = same & (np.diff(d_seq[o]) < 0)
    np.logical_or.at(bad_msg, m[o][1:][breaks], True)

    numbers = {
        "wrong_delivery_sets": int(wrong.sum()),
        "stray_deliveries": stray,
        "topic_or_payload_mismatches": int((topic_bad | pay_bad).sum()),
        "missing_pubacks": int(unacked.sum()),
        "order_breaks": int(breaks.sum()),
        "oracle_vs_plain_mismatches": brute_force(pop, key_s, n_sample, seed),
    }
    out = {k: (v, 0) for k, v in numbers.items()}
    correct = not any(numbers.values())
    failed = int(bad_msg.sum()) + stray
    if not correct and failed == 0:
        failed = 1              # the oracle itself is at fault
    return {"numbers": out, "correct": bool(correct) and M > 0,
            "attempted": M, "failed": failed,
            "info": {"dup_redeliveries": int(sub["dup"].sum()),
                     "deliveries": int(len(m)),
                     "expected_deliveries": int(has.sum())}}
