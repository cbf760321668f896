"""Controls: one stated guarantee broken underneath the timed path.

The benchmark's own runs never use these. `run.py --control <name>`
(and the tests under `benchmark/tests/`) switch one on to show that the
comparison which decides `correct` comes out false when the broker
loses, duplicates or reorders a delivery, or picks a group's member at
random instead of in turn. The first three tamper where a delivery is
produced: `Session.deliver`, which every route path (host, device,
lanes) ends in; `random_pick` switches the node's
`shared_subscription_strategy` to `random`, which the host pick and the
device's route programs both read at every window.
"""

from __future__ import annotations

ONE_IN = 997          # a delivery in this many is tampered with


def apply(name: str, node):
    """Patch the program for control `name`; returns an undo function."""
    from emqx_tpu.broker.session import Session
    if name == "random_pick":
        broker = node.broker
        was, broker.shared_strategy = broker.shared_strategy, "random"

        def turn_back():
            broker.shared_strategy = was
        return turn_back
    real = Session.deliver
    state = {"n": 0, "held": {}}

    def lose(self, msgs):
        keep = []
        for pair in msgs:
            state["n"] += 1
            if state["n"] % ONE_IN:
                keep.append(pair)
        return real(self, keep)

    def duplicate(self, msgs):
        out = []
        for pair in msgs:
            state["n"] += 1
            out.append(pair)
            if not state["n"] % ONE_IN:
                out.append((pair[0].copy(), pair[1]))
        return real(self, out)

    def reorder(self, msgs):
        """Hold a delivery back until the next one of the same
        publisher, topic and qos has passed it."""
        out = []
        held = state["held"]
        for pair in msgs:
            m = pair[0]
            key = (id(self), m.from_, m.topic, m.qos)
            state["n"] += 1
            if key in held:
                out += [pair, held.pop(key)]
            elif not state["n"] % 97 and len(held) < 4096:
                held[key] = pair
            else:
                out.append(pair)
        return real(self, out)

    patched = {"lose": lose, "duplicate": duplicate, "reorder": reorder}
    if name not in patched:
        raise ValueError(f"unknown control {name!r}")
    Session.deliver = patched[name]

    def undo():
        Session.deliver = real
    return undo
