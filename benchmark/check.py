"""The comparison that decides `correct`: every message against the oracle.

Inputs are the publishers' send log and the subscribers' receive log (as
`loadgen.py` writes them) and the configuration's population. Each
number compared is returned beside its limit; `correct` is all of them
within limits. A limit is 0 unless the configuration's `limits` names
the number. The oracle is the population's closed form, cross-checked
on a seeded sample of the topics sent by brute force over every filter
with `plain.py`.
"""

from __future__ import annotations

import zlib

import numpy as np

from benchmark import plain, populations

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
    groups: dict = {}           # filter -> {group name: [members]}
    for c in range(pop.conns):
        for f, _q in pop.subscriptions(c):
            name, real = plain.split_share(f)
            if name is None:
                owners.setdefault(f, []).append(c)
            else:
                groups.setdefault(real, {}).setdefault(name, []).append(c)
    filters = pop.filters()
    split = [f.split("/") for f in filters]
    want = pop.expect(pick)
    shared = populations.members(pop, pick)
    bad = 0
    for k, key in enumerate(pick):
        hit = [filters[fi]
               for fi in plain.matching(pop.topic(int(key)), split)]
        got = [c for f in hit for c in owners.get(f, [])]
        wrong = sorted(got) != sorted(int(x) for x in want[k] if x >= 0)
        sets = sorted(sorted(g) for f in hit
                      for g in groups.get(f, {}).values())
        mine = [] if shared is None else sorted(
            sorted(int(x) for x in g if x >= 0)
            for g in shared[k] if (g >= 0).any())
        bad += wrong or sets != mine
    return bad


def out_of_turn(picks: np.ndarray, seats: np.ndarray) -> float:
    """Per group, the picks beyond a one-pick difference between its
    members (`seats`: which columns of `picks` [groups, M] are members),
    summed over the groups."""
    least = np.where(seats, picks, np.inf).min(axis=1, initial=np.inf)
    return float(np.where(seats, picks - least[:, None] - 1, 0)
                 .clip(min=0).sum())


def shared_deliveries(pop, keys, shared: np.ndarray, extra: np.ndarray,
                      seed: int) -> dict:
    """The group half of the delivery sets. `shared` [messages, G, M] is
    the population's `expect_shared(keys)`, `extra` [messages, conns]
    what each connection got beyond its plain subscriptions. Returns
    `wrong` [messages] (not exactly one delivery inside each matching
    group's members, or one outside them all), `member_of` [messages,
    conns] (is the connection a member of a group the message matches)
    and two readings of round robin over the run. `rr_excess_share`:
    the picks out of turn (`out_of_turn`) as a share of all shared
    deliveries; members picked at random read 0.8 / sqrt(picks a group),
    so it falls as a run routes more. `rr_excess_vs_random`: the same
    picks out of turn over those of one seeded draw in which every
    group's own number of picks falls on its members at random: about 1
    for a random pick whatever the run's length, 0 for strict turns."""
    M, n_subs = extra.shape
    n_mem = shared.shape[2]
    valid = shared >= 0
    at = np.where(valid, shared, 0)
    rows = np.arange(M)[:, None, None]
    member_of = np.bincount((rows * n_subs + at)[valid],
                            minlength=M * n_subs).reshape(M, n_subs)
    if member_of.max(initial=0) > 1:
        raise ValueError("two groups matching one key share a member")
    took = np.where(valid, extra[rows, at], 0)
    wrong = ((extra > 0) & (member_of == 0)).any(axis=1) \
        | (took.sum(axis=2) != valid.any(axis=2)).any(axis=1)
    # picks[group, member] over the run
    uniq, inv = np.unique(np.asarray(pop.group_ids(keys)),
                          return_inverse=True)
    slot = inv.reshape(M, -1, 1) * n_mem + np.arange(n_mem)
    size = len(uniq) * n_mem
    picks = np.bincount(slot[valid], np.maximum(took, 0)[valid], size) \
        .reshape(-1, n_mem)
    seats = np.bincount(slot[valid], minlength=size).reshape(-1, n_mem) > 0
    picks, seats = picks[seats.any(axis=1)], seats[seats.any(axis=1)]
    beyond = out_of_turn(picks, seats)
    at_random = out_of_turn(
        np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, 11]).multinomial(
            picks.sum(axis=1).astype(np.int64),
            seats / seats.sum(axis=1, keepdims=True)), seats)
    return {"wrong": wrong, "member_of": member_of,
            "rr_excess_share": beyond / max(1.0, float(picks.sum())),
            "rr_excess_vs_random": beyond / max(1.0, at_random)}


def check(pop, pub: dict, sub: dict, seed: int, n_sample: int = 16,
          limits: dict | None = None) -> dict:
    """Returns {"numbers": {name: (value, limit)}, "correct": bool,
    "attempted": n, "failed": n, "info": {...}}. A comparison is exact,
    its limit 0, unless `limits` (the configuration's) names it."""
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
    extra = got - exp
    shared = populations.members(pop, key_s)
    if shared is None:
        groups = None
        wrong = (extra != 0).any(axis=1)
    else:
        groups = shared_deliveries(pop, key_s, shared, extra, seed)
        wrong = (extra < 0).any(axis=1) | groups["wrong"]
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
    info = {"dup_redeliveries": int(sub["dup"].sum()),
            "deliveries": int(len(m)),
            "expected_deliveries": populations.expected_count(pop, key_s)}
    if groups is not None:
        # a group's deliveries: QoS min(publish, subscription), and its
        # members picked in turn
        asked = np.where(groups["member_of"][m, d_sub] > 0,
                         pop.sub_qos["shared"], pop.sub_qos["plain"])
        qos_bad = d_qos != np.minimum(qos_s[m], asked)
        np.logical_or.at(bad_msg, m[qos_bad], True)
        numbers.update(delivery_qos_mismatches=int(qos_bad.sum()),
                       rr_excess_vs_random=groups["rr_excess_vs_random"])
        info["rr_excess_share"] = groups["rr_excess_share"]
    limits = limits or {}
    if set(limits) - set(numbers):
        raise ValueError(f"limits for {sorted(set(limits) - set(numbers))}, "
                         f"which this population does not compare")
    out = {k: (v, limits.get(k, 0)) for k, v in numbers.items()}
    correct = all(v <= lim for v, lim in out.values())
    failed = int(bad_msg.sum()) + stray
    if not correct and failed == 0:
        failed = 1              # the oracle itself is at fault
    return {"numbers": out, "correct": bool(correct) and M > 0,
            "attempted": M, "failed": failed, "info": info}
