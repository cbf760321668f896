"""The benchmark's own code, checked on the CPU.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

- the codec agrees with `emqx_tpu.mqtt.frame` at the byte level, both ways;
- `plain.py` agrees with `emqx_tpu.utils.topic.match` on a seeded set, and
  both populations' closed forms agree with brute force;
- the ledger check passes a sound log and fails on a lost, a duplicated,
  a reordered and a twice-picked delivery, and on an unacknowledged
  QoS 1 PUBLISH; with `$share` groups, on a message both members, neither
  or a non-member got, on a downgraded QoS and on picks out of turn;
- the key draws are the seed's: `uniform` bit for bit what it was, `zipf`
  by its law;
- the trace reduction on a hand-made trace and on the small recorded one;
- the loader refuses a cell whose per-layer metric lacks its `moves` metric.
"""

import json
import os
import shutil
import zlib

import numpy as np
import pytest

from benchmark import check, codec, manifest, plain, populations, traffic_gen
from benchmark.populations import device_share, site_plus
from benchmark.readers import counter, telemetry, xplane

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------------------------ codec

def test_codec_matches_the_programs_serializer_byte_for_byte():
    from emqx_tpu.mqtt import frame, packet as P
    pay = bytes(range(256))
    cases = [
        (codec.connect("bench-sub3"),
         P.Connect(clientid="bench-sub3", keepalive=0, clean_start=True)),
        (codec.publish("device/d1/x/n2/t", pay, 0),
         P.Publish(topic="device/d1/x/n2/t", payload=pay, qos=0)),
        (codec.publish("site/s1/line/l2/d3/m4", pay, 1, 65535),
         P.Publish(topic="site/s1/line/l2/d3/m4", payload=pay, qos=1,
                   packet_id=65535)),
        (codec.puback(77), P.Puback(packet_id=77)),
        (codec.subscribe(9, [("$share/bg/device/d1/+/n2/#", 1),
                             ("device/d2/+/n2/#", 0)]),
         P.Subscribe(packet_id=9, filters=[
             ("$share/bg/device/d1/+/n2/#", P.SubOpts(qos=1)),
             ("device/d2/+/n2/#", P.SubOpts(qos=0))])),
        (codec.unsubscribe(10, ["a/+/b", "c/#"]),
         P.Unsubscribe(packet_id=10, filters=["a/+/b", "c/#"])),
        (codec.PINGREQ_FRAME, P.Pingreq()),
    ]
    for mine, pkt in cases:
        assert mine == frame.serialize(pkt), pkt
    # a 512-entry SUBSCRIBE needs a 3-byte remaining length
    big = [(f"device/d{i}/+/n{i}/#", i % 2) for i in range(512)]
    assert codec.subscribe(1, big) == frame.serialize(P.Subscribe(
        packet_id=1, filters=[(f, P.SubOpts(qos=q)) for f, q in big]))


def test_codec_reads_what_the_program_writes():
    from emqx_tpu.mqtt import frame, packet as P
    pay = b"\x05\x00" + bytes(254)
    wire = b"".join(frame.serialize(p) for p in [
        P.Connack(), P.Suback(packet_id=3, reason_codes=[0, 1, 0x80]),
        P.Publish(topic="device/d9/x/n1/t", payload=pay, qos=1,
                  packet_id=300, dup=True),
        P.Publish(topic="t", payload=pay, qos=0), P.Puback(packet_id=12)])
    got = list(codec.scan(wire + b"\x30"))         # plus a broken tail
    assert [t for t, *_ in got] == [codec.CONNACK, codec.SUBACK,
                                    codec.PUBLISH, codec.PUBLISH,
                                    codec.PUBACK]
    _t, _f, a, b = got[1]
    assert wire[a:b] == b"\x00\x03\x00\x01\x80"
    _t, fl, a, b = got[2]
    topic, qos, dup, retain, pid, p = codec.parse_publish(wire, fl, a, b)
    assert (topic, qos, dup, retain, pid) == \
        (b"device/d9/x/n1/t", 1, True, False, 300)
    assert wire[p:b] == pay
    # and the program's parser reads the codec's frames in small chunks
    parser = frame.FrameParser()
    mine = codec.publish("a/b", pay, 1, 7) + codec.puback(7)
    pkts = []
    for k in range(0, len(mine), 5):
        pkts += parser.feed(mine[k:k + 5])
    assert (pkts[0].topic, pkts[0].qos, pkts[0].packet_id,
            pkts[0].payload) == ("a/b", 1, 7, pay)
    assert pkts[1].packet_id == 7


def test_varint_edges():
    for n in (0, 127, 128, 16383, 16384, 2097151, 2097152, 268435455):
        frame = b"\x30" + codec.varint(n)
        assert list(codec.scan(frame + bytes(n)))[0][3] == len(frame) + n
    with pytest.raises(ValueError):
        codec.varint(268435456)


# ------------------------------------------------- matcher and populations

def test_plain_matcher_agrees_with_the_programs_on_a_seeded_set():
    from emqx_tpu.utils import topic as T
    rng = np.random.default_rng(11)
    words = ["a", "b", "dev", "$SYS", "+", "#", "x1", ""]
    n = 0
    for _ in range(4000):
        t = "/".join(rng.choice(words[:5], rng.integers(1, 5)))
        levels = list(rng.choice(words, rng.integers(1, 5)))
        if "#" in levels[:-1]:
            continue
        f = "/".join(levels)
        if "+" in t or not t:
            continue
        assert plain.match(t, f) == bool(T.match(t, f)), (t, f)
        n += 1
    assert n > 1000
    assert plain.match("a", "a/#") and not plain.match("$SYS/x", "+/x")
    assert not plain.match("$SYS/x", "#") and plain.match("$SYS/x", "$SYS/#")


SMALL = {"sites": 4, "lines": 3, "devs": 4, "meas": 5, "b_meas": 2}


def _pop(conns=6):
    return site_plus.Population(SMALL, conns)


def test_closed_form_equals_brute_force():
    pop = _pop()
    keys = np.arange(int(np.prod(pop.dims)))
    assert check.brute_force(pop, keys, len(keys), seed=5) == 0
    subs = sum(len(pop.subscriptions(c)) for c in range(pop.conns))
    assert len(pop.filters()) == subs
    per_key = (pop.expect(keys) >= 0).sum(axis=1)
    assert set(per_key) == {2, 3}


def test_brute_force_sees_a_wrong_closed_form():
    pop = _pop()
    real = pop.expect

    def off_by_one(keys):
        want = real(keys)
        return (want + (want >= 0)) % pop.conns - (want < 0)
    pop.expect = off_by_one
    assert check.brute_force(pop, np.arange(240), 64, seed=1) > 0


SMALL_SHARE = {"ids": 8, "nums": 25, "shared_pct": 50, "group": "bg",
               "members": 2}


def _share_pop(conns=6, **over):
    return device_share.Population({**SMALL_SHARE, **over}, conns)


def test_closed_form_equals_brute_force_with_groups():
    for pop in (_share_pop(), _share_pop(conns=16, members=3)):
        keys = np.arange(int(np.prod(pop.dims)))
        assert check.brute_force(pop, keys, len(keys), seed=5) == 0
        shared = pop.expect_shared(keys)
        in_group = (shared >= 0).any(axis=(1, 2))
        assert in_group.sum() == len(keys) // 2
        # a key is a plain filter's or a group's, never both or neither
        assert ((pop.expect(keys) >= 0).sum(axis=1) + in_group == 1).all()
        assert populations.expected_count(pop, keys) == len(keys)
        assert (pop.group_ids(keys)[:, 0] >= 0).sum() == in_group.sum()
        assert len(pop.filters()) == len(keys)
        subs = sum(len(pop.subscriptions(c)) for c in range(pop.conns))
        assert subs == len(keys) // 2 * (1 + pop.members)
    # the rehearsal's size is SMALL_SHARE's
    with open(os.path.join(manifest.HERE, "configs",
                           "share50-250k.json")) as f:
        cfg = json.load(f)
    assert cfg["rehearse"]["population"] == {
        k: SMALL_SHARE[k] for k in ("ids", "nums")}


@pytest.mark.parametrize("fault", ["second_member", "plain_as_group"])
def test_brute_force_sees_a_wrong_closed_form_of_the_groups(fault):
    pop = _share_pop()
    real = pop.expect_shared
    if fault == "second_member":
        def wrong(keys):
            out = real(keys)
            out[:, 0, 1] = np.where(out[:, 0, 1] >= 0,
                                    (out[:, 0, 1] + 1) % pop.conns, -1)
            return out
    else:
        def wrong(keys):
            return np.full((len(keys), 1, 2), -1)
    pop.expect_shared = wrong
    assert check.brute_force(pop, np.arange(200), 64, seed=1) > 0


def test_plain_splits_a_shared_subscription_as_the_specification_says():
    assert plain.split_share("$share/bg/device/d1/+/n2/#") == \
        ("bg", "device/d1/+/n2/#")
    assert plain.split_share("$share/g//a") == ("g", "/a")
    assert plain.split_share("device/$share/x") == (None, "device/$share/x")
    for bad in ("$share/g", "$share//a", "$share/g+/a", "$share/#/a"):
        with pytest.raises(ValueError):
            plain.split_share(bad)


def test_full_size_populations_have_the_stated_counts():
    with open(os.path.join(manifest.HERE, "configs", "plus-100k.json")) as f:
        cfg = json.load(f)
    pop = site_plus.Population(cfg["population"]["params"],
                               cfg["connections"]["subscribers"])
    assert cfg["filters"] == cfg["subscriptions"] == 100_000
    assert sum(len(pop.subscriptions(c)) for c in range(pop.conns)) == 100_000
    assert int(np.prod(pop.dims)) == 3_600_000
    with open(os.path.join(manifest.HERE, "configs",
                           "share50-250k.json")) as f:
        cfg = json.load(f)
    pop = populations.load(cfg)
    assert cfg["filters"] == len(pop.filters()) == 250_000
    assert sum(len(pop.subscriptions(c)) for c in range(pop.conns)) \
        == cfg["subscriptions"] == 375_000
    keys = np.arange(250_000)
    assert (pop.expect_shared(keys) >= 0).any(axis=(1, 2)).sum() == 125_000
    assert pop.topic(499 * 500 + 7) == "device/d499/x/n7/t"
    assert plain.match(pop.topic(1234), pop.filters()[1234])


def test_traffic_is_the_seeds():
    uniform = {"dist": "uniform"}
    a = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 9, 1), 1000,
                              (500, 500), uniform)
    b = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 9, 1), 1000,
                              (500, 500), uniform)
    c = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 10, 1), 1000,
                              (500, 500), uniform)
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 250_000
    with pytest.raises(ValueError):
        traffic_gen.draw_keys(traffic_gen.rng_for(1, 1), 4, (5,),
                              {"dist": "other"})


def test_the_uniform_draw_is_bit_for_bit_the_parents():
    """Pinned from the parent's `draw_keys` (PR 25's tree): the same
    seed gives `plus-100k.flood` the same topics as before."""
    keys = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 9, 1), 1000,
                                 (100, 30, 30, 40), {"dist": "uniform"})
    assert keys.dtype == np.int64
    assert keys[:4].tolist() == PARENT_UNIFORM_HEAD
    assert zlib.crc32(keys.tobytes()) == PARENT_UNIFORM_CRC


PARENT_UNIFORM_HEAD = [3016026, 216480, 3046960, 2367873]
PARENT_UNIFORM_CRC = 2879118003


def _zeta(s, n=10**6):
    k = np.arange(1, n + 1, dtype=np.float64)
    return float((k ** -s).sum() + n ** (1 - s) / (s - 1) - 0.5 * n ** -s)


def test_the_zipf_draw_is_the_seeds_and_follows_its_law():
    spec = {"dist": "zipf", "s": 1.3, "dim": 0}
    n, d = 100_000, 500
    a = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 9, 1), n,
                              (d, 500), spec)
    b = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 9, 1), n,
                              (d, 500), spec)
    c = traffic_gen.draw_keys(traffic_gen.rng_for(2**31 + 10, 1), n,
                              (d, 500), spec)
    assert (a == b).all() and (a != c).any()
    ids, nums = np.divmod(a, 500)
    assert ids.min() == 0 and ids.max() == d - 1
    # min(zipf(s) - 1, d - 1): rank r has mass (r + 1)^-s / zeta(s), and
    # the last index takes the whole tail beyond it
    z = _zeta(1.3)
    first = 1.0 / z
    last = 1.0 - float((np.arange(1, d, dtype=np.float64) ** -1.3).sum()) / z
    for got, p in (((ids == 0).mean(), first), ((ids == 1).mean(),
                   2 ** -1.3 / z), ((ids == d - 1).mean(), last)):
        assert abs(got - p) < 5 * np.sqrt(p * (1 - p) / n), (got, p)
    assert 0.12 < last < 0.14 and 0.25 < first < 0.26
    # the other dimension is uniform
    assert abs(nums.mean() - 249.5) < 5 * 144.3 / np.sqrt(n)
    # the skewed dimension is the one the file names
    other = traffic_gen.draw_keys(traffic_gen.rng_for(3, 1), n, (500, d),
                                  {"dist": "zipf", "s": 1.3, "dim": 1})
    assert abs((other % d == 0).mean() - first) < 0.01
    assert abs((other // d == 0).mean() - 1 / 500) < 0.002


# ------------------------------------------------------------- the ledger

def sound_logs(pop, n=4000, pubs=4, seed=2, dist=None):
    """Logs of a broker that keeps every guarantee."""
    rng = np.random.default_rng(seed)
    keys = traffic_gen.draw_keys(rng, n, pop.dims,
                                 dist or {"dist": "uniform"})
    pub = {"pub": np.repeat(np.arange(pubs), n // pubs).astype(np.int32),
           "seq": np.tile(np.arange(n // pubs), pubs).astype(np.int64),
           "key": keys, "qos": np.zeros(n, np.int8)}
    pub["qos"][pub["seq"] % 4 == 0] = 1
    pub["due_ns"] = np.arange(n, dtype=np.int64) * 1000 + 10**9
    pub["send_ns"] = pub["due_ns"] + 5
    pub["ack_ns"] = np.where(pub["qos"] == 1, pub["due_ns"] + 900, 0)
    want = pop.expect(keys)
    shared = populations.members(pop, keys)
    asks = getattr(pop, "sub_qos", {"plain": 1, "shared": 1})
    turn: dict = {}              # group -> picks so far: members in turn
    rows = []
    for m in range(n):
        to = [(int(c), asks["plain"]) for c in want[m] if c >= 0]
        for g in range(0 if shared is None else shared.shape[1]):
            who = [int(c) for c in shared[m, g] if c >= 0]
            if who:
                gid = int(pop.group_ids(keys[m:m + 1])[0, g])
                turn[gid] = turn.get(gid, -1) + 1
                to.append((who[turn[gid] % len(who)], asks["shared"]))
        crc = zlib.crc32(pop.topic(int(keys[m])).encode())
        for c, ask in to:
            rows.append((c, pub["pub"][m], pub["seq"][m], pub["due_ns"][m],
                         pub["due_ns"][m] + 700, min(pub["qos"][m], ask),
                         False, crc))
    cols = list(zip(*rows))
    sub = {"sub": np.array(cols[0], np.int16),
           "pub": np.array(cols[1], np.int32),
           "seq": np.array(cols[2], np.int64),
           "due_ns": np.array(cols[3], np.int64),
           "recv_ns": np.array(cols[4], np.int64),
           "qos": np.array(cols[5], np.int8),
           "dup": np.array(cols[6], np.bool_),
           "crc": np.array(cols[7], np.uint32)}
    return pub, sub


def _drop(sub, rows):
    keep = np.ones(len(sub["sub"]), bool)
    keep[rows] = False
    return {k: v[keep] for k, v in sub.items()}


def test_sound_logs_pass():
    for pop in (_pop(), _pop(conns=4)):
        pub, sub = sound_logs(pop)
        v = check.check(pop, pub, sub, seed=1)
        assert v["correct"], v["numbers"]
        assert v["failed"] == 0 and v["attempted"] == 4000
        assert all(val == 0 for val, _lim in v["numbers"].values())


def tamper_lost(pop, pub, sub):
    return pub, _drop(sub, [17]), "wrong_delivery_sets"


def tamper_duplicated(pop, pub, sub):
    twice = {k: np.concatenate([v, v[40:41]]) for k, v in sub.items()}
    return pub, twice, "wrong_delivery_sets"


def tamper_reordered(pop, pub, sub):
    # two deliveries of one (subscriber, publisher, topic, qos) swapped
    seen: dict = {}
    for r in range(len(sub["sub"])):
        k = (sub["sub"][r], sub["pub"][r], sub["crc"][r], sub["qos"][r])
        if k in seen and sub["seq"][seen[k]] != sub["seq"][r]:
            a = seen[k]
            order = np.arange(len(sub["sub"]))
            order[[a, r]] = order[[r, a]]
            return pub, {c: v[order] for c, v in sub.items()}, \
                "order_breaks"
        seen.setdefault(k, r)
    raise AssertionError("no repeated topic in the log")


def tamper_wrong_subscriber(pop, pub, sub):
    out = {k: v.copy() for k, v in sub.items()}
    out["sub"][9] = (out["sub"][9] + 1) % pop.conns
    return pub, out, "wrong_delivery_sets"


def tamper_unacknowledged(pop, pub, sub):
    out = {k: v.copy() for k, v in pub.items()}
    out["ack_ns"][np.flatnonzero(pub["qos"] == 1)[3]] = 0
    return out, sub, "missing_pubacks"


def tamper_stray(pop, pub, sub):
    out = {k: np.concatenate([v, v[:1]]) for k, v in sub.items()}
    out["seq"][-1] = 10**6
    return pub, out, "stray_deliveries"


def tamper_wrong_topic(pop, pub, sub):
    out = {k: v.copy() for k, v in sub.items()}
    out["crc"][5] ^= 1
    return pub, out, "topic_or_payload_mismatches"


@pytest.mark.parametrize("tamper", [
    tamper_lost, tamper_duplicated, tamper_reordered,
    tamper_wrong_subscriber, tamper_unacknowledged, tamper_stray,
    tamper_wrong_topic], ids=lambda f: f.__name__[7:])
def test_a_weakened_guarantee_fails_the_check(tamper):
    pop = _pop()
    pub, sub, number = tamper(pop, *sound_logs(pop))
    v = check.check(pop, pub, sub, seed=1)
    assert not v["correct"]
    assert v["failed"] >= 1
    value, limit = v["numbers"][number]
    assert value > limit, v["numbers"]


def test_a_retransmission_marked_dup_is_not_a_second_delivery():
    pop = _pop()
    pub, sub = sound_logs(pop)
    q1 = np.flatnonzero(sub["qos"] == 1)[:3]
    again = {k: np.concatenate([v, v[q1]]) for k, v in sub.items()}
    again["dup"][-3:] = True
    v = check.check(pop, pub, again, seed=1)
    assert v["correct"] and v["info"]["dup_redeliveries"] == 3


# ------------------------------------------------- the ledger, with groups

ZIPF = {"dist": "zipf", "s": 1.3, "dim": 0}
LIMITS = {"rr_excess_vs_random": 0.3}


def test_sound_logs_with_groups_pass():
    for pop in (_share_pop(), _share_pop(conns=16, members=3)):
        pub, sub = sound_logs(pop, dist=ZIPF)
        v = check.check(pop, pub, sub, seed=1, limits=LIMITS)
        assert v["correct"], v["numbers"]
        assert v["failed"] == 0 and v["attempted"] == 4000
        assert v["numbers"]["rr_excess_vs_random"] == (0.0, 0.3)
        assert v["info"]["rr_excess_share"] == 0.0
        assert all(val == 0 for val, _lim in v["numbers"].values())
        assert v["info"]["deliveries"] == v["info"]["expected_deliveries"] \
            == 4000
        assert (sub["qos"] == 1).any() and (sub["qos"] == 0).any()


def test_without_limits_every_limit_is_zero():
    with open(os.path.join(manifest.HERE, "configs", "plus-100k.json")) as f:
        assert "limits" not in json.load(f)
    for pop in (_pop(), _share_pop()):
        v = check.check(pop, *sound_logs(pop), seed=1, limits=None)
        assert v["correct"]
        assert {lim for _val, lim in v["numbers"].values()} == {0}
    # the group numbers are compared where there are groups, only there
    assert "rr_excess_vs_random" not in check.check(
        _pop(), *sound_logs(_pop()), seed=1)["numbers"]
    with pytest.raises(ValueError, match="does not compare"):
        check.check(_pop(), *sound_logs(_pop()), seed=1, limits=LIMITS)
    # one pick out of turn is over a limit of 0, and under the file's
    pop = _share_pop()
    pub, sub, _number = tamper_one_member_takes_all(pop, *sound_logs(pop),
                                                    groups=1)
    assert not check.check(pop, pub, sub, seed=1)["correct"]
    assert check.check(pop, pub, sub, seed=1, limits=LIMITS)["correct"]


def _shared_rows(pop, pub, sub):
    """Rows of the receive log that are a group's deliveries, with the
    members of that group and the message's key."""
    per_pub = int(pub["seq"].max()) + 1
    keys = pub["key"][sub["pub"].astype(np.int64) * per_pub + sub["seq"]]
    who = pop.expect_shared(keys)[:, 0, :]
    rows = np.flatnonzero(who[:, 0] >= 0)
    return rows, who[rows], keys[rows]


def tamper_both_members(pop, pub, sub):
    rows, who, _keys = _shared_rows(pop, pub, sub)
    out = {k: np.concatenate([v, v[rows[:1]]]) for k, v in sub.items()}
    out["sub"][-1] = who[0][who[0] != sub["sub"][rows[0]]][0]
    return pub, out, "wrong_delivery_sets"


def tamper_non_member(pop, pub, sub):
    rows, who, _keys = _shared_rows(pop, pub, sub)
    out = {k: v.copy() for k, v in sub.items()}
    out["sub"][rows[3]] = (who[3].max() + 2) % pop.conns
    assert out["sub"][rows[3]] not in who[3]
    return pub, out, "wrong_delivery_sets"


def tamper_neither_member(pop, pub, sub):
    rows, _who, _keys = _shared_rows(pop, pub, sub)
    return pub, _drop(sub, rows[5:6]), "wrong_delivery_sets"


def tamper_one_member_takes_all(pop, pub, sub, groups=None):
    """Every pick of a group (of the first `groups` ones) goes to its
    first member: each delivery set is lawful, the turns are not."""
    rows, who, keys = _shared_rows(pop, pub, sub)
    out = {k: v.copy() for k, v in sub.items()}
    keep = np.isin(keys, np.unique(keys)[:groups])
    out["sub"][rows[keep]] = who[keep, 0]
    return pub, out, "rr_excess_vs_random"


def tamper_downgraded_qos(pop, pub, sub):
    rows, _who, _keys = _shared_rows(pop, pub, sub)
    out = {k: v.copy() for k, v in sub.items()}
    out["qos"][rows[sub["qos"][rows] == 1][0]] = 0
    return pub, out, "delivery_qos_mismatches"


@pytest.mark.parametrize("tamper", [
    tamper_both_members, tamper_non_member, tamper_neither_member,
    tamper_one_member_takes_all, tamper_downgraded_qos, tamper_lost,
    tamper_duplicated, tamper_reordered, tamper_unacknowledged],
    ids=lambda f: f.__name__[7:])
def test_a_weakened_guarantee_fails_the_check_with_groups(tamper):
    pop = _share_pop()
    pub, sub, number = tamper(pop, *sound_logs(pop, dist=ZIPF))
    v = check.check(pop, pub, sub, seed=1, limits=LIMITS)
    assert not v["correct"]
    assert v["failed"] >= 1
    value, limit = v["numbers"][number]
    assert value > limit, v["numbers"]
    if number == "rr_excess_vs_random":
        # the sets are lawful: only the turns give it away
        assert v["numbers"]["wrong_delivery_sets"][0] == 0 and value > 1.5
        assert v["info"]["rr_excess_share"] > 0.3


def test_two_groups_of_one_message_may_not_share_a_member():
    pop = _share_pop()
    real = pop.expect_shared
    pop.expect_shared = lambda keys: np.concatenate(
        [real(keys), real(keys)], axis=1)
    pop.group_ids = lambda keys: np.zeros((len(keys), 2), np.int64)
    with pytest.raises(ValueError, match="share a member"):
        check.check(pop, *sound_logs(_share_pop()), seed=1)


# ------------------------------------------------------------ the readers

def small_trace():
    us = 1000.0
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_route_window(1)", 100 * us, 300 * us],
                ["jit_other(2)", 600 * us, 100 * us]]},
            {"name": "XLA Ops", "events": [
                ["fusion.1", 100 * us, 100 * us],
                ["fusion.1", 150 * us, 100 * us],      # overlaps the first
                ["copy.2", 300 * us, 100 * us],
                ["fusion.9", 600 * us, 100 * us],
                ["late", 2000 * us, 50 * us]]},        # outside the window
            {"name": "Steps", "events": [["0", 0.0, 900 * us]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                ["bench:trace_window", 0.0, 1000 * us],
                ["bench:dispatch", 50 * us, 60 * us],
                ["bench:materialize", 380 * us, 200 * us],
                ["$other", 0.0, 1000 * us]]}]}]}


def test_trace_reduction_on_a_hand_made_trace():
    r = xplane.reduce(small_trace())
    assert r["window_s"] == pytest.approx(1e-3)
    # union: [100,250] + [300,400] + [600,700] us
    assert r["busy_s"] == pytest.approx(350e-6)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(200e-6) and "late" not in ops
    gaps = dict(r["idle_gaps"])
    # gaps: [0,100] [250,300] [400,600] [700,1000] us
    assert gaps["bench:dispatch"] == pytest.approx(50e-6)
    assert gaps["bench:materialize"] == pytest.approx(180e-6)
    assert sum(gaps.values()) == pytest.approx(650e-6)
    seconds, n = xplane.program_seconds(small_trace(), ["route"])
    assert (seconds, n) == (pytest.approx(300e-6), 1)


def _no_tpu_plane(t):
    t["planes"] = t["planes"][1:]


def _tpu_plane_without_lines(t):
    t["planes"][0]["lines"] = []


def _only_the_profilers_own_planes_of_the_chip(t):
    """What an idle v5e leaves (my chip run, PR 26)."""
    t["planes"][0] = {"name": "#Chip0 Misc", "lines": []}
    t["planes"].insert(0, {"name": "/device:CUSTOM:Megascale Trace",
                           "lines": []})


def _nothing_ran_in_the_window(t):
    for ln in t["planes"][0]["lines"]:
        ln["events"] = [ev for ev in ln["events"] if ev[0] == "late"]


@pytest.mark.parametrize("cut,idle", [
    (_no_tpu_plane, None), (_tpu_plane_without_lines, 100.0),
    (_only_the_profilers_own_planes_of_the_chip, 100.0),
    (_nothing_ran_in_the_window, 100.0)], ids=lambda x: getattr(
        x, "__name__", None))
def test_trace_without_a_device_plane_is_refused(cut, idle):
    """No plane of a TPU at all: refused. A chip the profiler watched
    and on which no operation ran in the window: a reading, idle 100 %."""
    from benchmark.readers import trace_idle, trace_program, trace_spans
    t = small_trace()
    cut(t)
    if idle is None:
        with pytest.raises(ValueError, match="no plane of a TPU"):
            xplane.reduce(t)
        return
    r = xplane.reduce(t)
    assert r["busy_s"] == 0.0 and r["device_ops"] == [] and r["chips"] == 1
    assert r["window_s"] == pytest.approx(1e-3)
    # the whole window is a gap, shared out among the harness's spans
    gaps = dict(r["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(1e-3)
    assert gaps["bench:dispatch"] == pytest.approx(60e-6)
    ctx = {"trace": t, "trace_reduced": r,
           "trace_m0": {"routing.device.batches": 7},
           "trace_m1": {"routing.device.batches": 7}}
    assert trace_idle.read(ctx) == idle
    # per-window device metrics: their denominators did not move
    assert trace_program.read(ctx, ["route"]) == 0.0
    assert trace_spans.read(ctx, unnamed=True) == pytest.approx(1000.0)


def test_trace_reduction_on_the_recorded_trace():
    """A cut of a real trace of `share50-250k.flood` on a TPU v5 lite
    (PR 23): the planes and lines the reduction counts on are there."""
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        t = json.load(f)
    assert xplane.device_planes(t)
    r = xplane.reduce(t)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    seconds, n = xplane.program_seconds(t, ["route"])
    assert n > 0 and 0 < seconds <= r["window_s"]


def test_counter_and_telemetry_readers():
    ctx = {"m0": {"a": 5, "pipeline.batches.host": 2},
           "m1": {"a": 25, "pipeline.batches.host": 6,
                  "pipeline.batches.device": 4},
           "window": {"publishes": 800, "seconds": 10.0},
           "tele0": {"stages": {"dispatch": {"sum_ms": 10.0, "count": 2}}},
           "tele1": {"stages": {"dispatch": {"sum_ms": 40.0, "count": 8}},
                     "rebuild": {"stages": {"build": {"mean_ms": 2000.0,
                                                      "count": 2}}}}}
    assert counter.read(ctx, ["a"], ["window.publishes"], 100.0) == 2.5
    assert counter.read(ctx, ["window.publishes"],
                        ["pipeline.batches.*"]) == 100.0
    assert counter.read(ctx, ["a"], ["nothing"]) == 0.0
    assert telemetry.read(ctx, "stages/dispatch/sum_ms",
                          "stages/dispatch/count") == 5.0
    assert telemetry.read(ctx, "stages/deliver/sum_ms",
                          "stages/deliver/count") == 0.0
    assert telemetry.read(ctx, "rebuild/stages/build/mean_ms",
                          mul="rebuild/stages/build/count", scale=0.001,
                          how="last") == 4.0


# ------------------------------------------------------------- the loader

def test_a_share_counted_at_two_moments_reads_as_counted():
    """`routing.device.windows` moves at prepare and
    `routing.device.cached_windows` at dispatch: a window prepared
    before the first snapshot and dispatched after it is in the
    numerator alone, and a short span then reads over 100. The reader
    does not cap it (a cap would hide a miscount), and no metric's file
    asks for one."""
    ctx = {"m0": {"routing.device.windows": 12,
                  "routing.device.cached_windows": 9},
           "m1": {"routing.device.windows": 14,
                  "routing.device.cached_windows": 12},
           "window": {"seconds": 3.0}}
    num, den = ["routing.device.cached_windows"], ["routing.device.windows"]
    assert counter.read(ctx, num, den, 100.0) == 150.0
    with pytest.raises(TypeError):
        counter.read(ctx, num, den, 100.0, cap=100.0)
    folder = os.path.join(os.path.dirname(HERE), "layer_metrics")
    for name in sorted(os.listdir(folder)):
        with open(os.path.join(folder, name)) as f:
            assert "cap" not in json.load(f).get("args", {}), name


def test_every_cell_of_the_manifest_loads():
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = manifest.Cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert all(m["moves"] in names for m in cell.per_layer)
        assert cell.chips == 1


def _copy_root(tmp_path):
    root = tmp_path / "root"
    root.mkdir()
    shutil.copy(os.path.join(manifest.ROOT, "BENCHMARK.json"), root)
    os.symlink(manifest.HERE, root / "benchmark")
    with open(root / "BENCHMARK.json") as f:
        return root, json.load(f)


def test_loader_refuses_a_metric_whose_moves_metric_the_cell_lacks(tmp_path):
    root, bench = _copy_root(tmp_path)
    cell = bench["workloads"][0]["name"]
    bench["end_to_end"].append({
        "name": "delivery_p99_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock", "workloads": []})
    bench["per_layer"][0].update(moves="delivery_p99_ms", workloads=[cell])
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    with pytest.raises(manifest.ManifestError, match="does not report"):
        manifest.Cell(cell, root=str(root))


def test_loader_refuses_what_the_traffic_mix_cannot_report(tmp_path):
    root, bench = _copy_root(tmp_path)
    cell = bench["workloads"][0]["name"]
    bench["end_to_end"].append({
        "name": "puback_p99_ms", "unit": "ms", "better": "lower",
        "bound": 0.1, "source": "host_clock", "workloads": [cell]})
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    with pytest.raises(manifest.ManifestError, match="traffic mix"):
        manifest.Cell(cell, root=str(root))
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.Cell("nothing.here", root=str(root))


# ------------------------------------------------- end-to-end arithmetic

def test_end_to_end_metrics_on_a_known_log():
    from benchmark import e2e
    n = 1000
    due = np.arange(n, dtype=np.int64) * 10**6            # 1 ms apart
    pub = {"qos": (np.arange(n) % 4 == 0).astype(np.int8), "due_ns": due,
           "send_ns": due + 200_000, "ack_ns": due + 3_000_000}
    sub = {"dup": np.zeros(n + 1, bool),
           "due_ns": np.append(due, due[5]),
           "recv_ns": np.append(due + 2_000_000 + np.arange(n) * 1000,
                                due[5] + 9 * 10**9)}
    sub["dup"][-1] = True                  # a retransmission: not counted
    t0, t1 = int(due[100]), int(due[600])
    assert e2e.delivered_per_s(pub, sub, t0, t1) == pytest.approx(
        ((sub["recv_ns"][:n] >= t0) & (sub["recv_ns"][:n] < t1)).sum() / 0.5)
    assert e2e.publishes_in(pub, t0, t1) == 500


def test_route_bytes_from_shapes():
    from benchmark.readers import route_bytes
    assert route_bytes.shapes_of(_pop().filters()) == 3
    assert route_bytes.shapes_of(["a/+/#", "b/+/#", "a/b", "+/b"]) == 3
    # 6 levels, 3 shapes, two deliveries
    assert route_bytes.message_bytes(6, 3, 2.0) == 32 + 576 + 16 + 20


def test_route_roofline_counts_messages_not_deliveries():
    """`messages.routed.device` counts deliveries; with a fan-out above
    1 the bytes are those of the PUBLISHes behind them."""
    from benchmark.readers import route_bytes, route_roofline
    pop = _pop()
    n = 6000
    keys = np.arange(n) % int(np.prod(pop.dims))
    per_msg = (pop.expect(keys) >= 0).sum() / n
    assert per_msg > 2
    ctx = {"trace": small_trace(),      # its route program takes 300 us
           "peaks": {"hbm_bytes_per_s": 1e9}, "pop": pop,
           "trace_m0": {"messages.routed.device": 1000},
           "trace_m1": {"messages.routed.device": 1000 + 500 * per_msg},
           "window": {"t0_ns": 0, "t1_ns": 10},
           "pub": {"key": keys, "send_ns": np.full(n, 5)}}
    need = 500 * route_bytes.message_bytes(6, 3, per_msg)
    assert route_roofline.read(ctx, ["route"]) == pytest.approx(
        100.0 * (need / 1e9) / 300e-6)
    ctx["trace_m1"] = ctx["trace_m0"]
    assert route_roofline.read(ctx, ["route"]) == 0.0


def test_the_set_ups_direct_warm_deliveries_are_left_out():
    pop = _pop()
    pub, sub = sound_logs(pop)
    extra = {k: np.concatenate([v, v[:7]]) for k, v in sub.items()}
    extra["pub"][-7:] = check.WARM_PUB
    v = check.check(pop, pub, extra, seed=1)
    assert v["correct"] and v["info"]["deliveries"] == len(sub["sub"])


# ------------------------------------------------- the generator's start

class _Sink:
    """A broker that answers CONNECT and reads everything else away."""

    def __init__(self):
        import socket
        import threading
        self.srv = socket.socket()
        self.srv.bind(("127.0.0.1", 0))
        self.srv.listen(16)
        self.port = self.srv.getsockname()[1]
        self.stop = False
        self.threads = [threading.Thread(target=self._accept, daemon=True)]
        self.threads[0].start()

    def _accept(self):
        import threading
        self.srv.settimeout(0.1)
        while not self.stop:
            try:
                c, _a = self.srv.accept()
            except OSError:
                continue
            t = threading.Thread(target=self._serve, args=(c,), daemon=True)
            t.start()
            self.threads.append(t)

    def _serve(self, c):
        c.settimeout(0.1)
        greeted = False
        while not self.stop:
            try:
                data = c.recv(1 << 16)
            except OSError:
                continue
            if not data:
                break
            if not greeted:
                c.sendall(bytes([codec.CONNACK << 4, 2, 0, 0]))
                greeted = True
        c.close()

    def close(self):
        self.stop = True
        for t in self.threads:
            t.join(2)
        self.srv.close()


def test_a_flood_brings_each_connection_up_at_its_own_start(tmp_path,
                                                            capsys):
    """`start_after_s` of a traffic mix: connection p writes nothing
    before t0 + start_after_s[p] (PR 37: `share50-250k` brings the
    flood's eight connections up in two halves)."""
    from benchmark import loadgen
    cell = manifest.Cell("plus-100k.flood")           # all QoS 0
    config = dict(cell.config)
    config["population"] = dict(config["population"], params=dict(
        config["population"]["params"], **config["rehearse"]["population"]))
    traffic = {"loop": "closed", "connections": 3, "fence_every": 0,
               "start_after_s": [0.0, 0.4, 0.4]}
    sink = _Sink()
    r, w = os.pipe()
    saved = os.dup(0)
    os.dup2(r, 0)                # the generator's command pipe is fd 0
    try:
        pubs = loadgen.Publishers(sink.port, config, traffic, 7,
                                  str(tmp_path))
        t0 = loadgen.now_ns() + int(0.05e9)
        pubs.run_cmd({"cmd": "run", "t0_ns": t0, "seconds": 0.8})
        col, n = pubs.log.col, pubs.log.n
        first = [int(col["send_ns"][:n][col["pub"][:n] == p].min()) - t0
                 for p in range(3)]
        # without the key all start at t0; a configuration sets it for
        # its own cell under the mix's name, and that wins
        mix = {"name": "flood", "loop": "closed", "connections": 3}
        plain = loadgen.Publishers(sink.port, config, mix, 7, str(tmp_path))
        assert plain.start_after == [0, 0, 0]
        own = dict(config, traffic={"flood": {"start_after_s": [0, .1, .2]},
                                    "other": {"start_after_s": [9, 9, 9]}})
        mine = loadgen.Publishers(sink.port, own, dict(
            mix, start_after_s=[0, 0, 0]), 7, str(tmp_path))
        assert mine.start_after == [0, int(.1e9), int(.2e9)]
        with pytest.raises(ValueError, match="names 2 connections"):
            loadgen.Publishers(sink.port, config,
                               dict(mix, start_after_s=[0, 0]), 7,
                               str(tmp_path))
        for s in pubs.socks + plain.socks + mine.socks:
            s.close()
    finally:
        os.dup2(saved, 0)
        for fd in (saved, r, w):
            os.close(fd)
        sink.close()
    assert 0 <= first[0] < int(0.2e9)
    assert int(0.4e9) <= first[1] < int(0.6e9)
    assert int(0.4e9) <= first[2] < int(0.6e9)
    assert json.loads(capsys.readouterr().out.strip())["sent"] == n


def test_only_share50_250k_sets_a_parameter_of_its_mix():
    """The two halves are the one cell's: every other configuration
    runs the flood mix as its file has it."""
    with open(os.path.join(manifest.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        with open(os.path.join(manifest.ROOT, c["file"])) as f:
            own = json.load(f).get("traffic")
        if c["name"] == "share50-250k":
            assert own == {"flood": {"start_after_s": [0.0] * 4 + [0.3] * 4}}
        else:
            assert own is None, c["name"]
