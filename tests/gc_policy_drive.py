"""`tests/test_gc_policy.py`'s child process: one rehearsal run of the
benchmark harness (`benchmark.run`, CPU backend) with the heap-freeze
policy forced to engage and a churn client at work in the window.

The floor is set below zero here, in the test's own process, so every
full collection after the one that follows a freeze (the base) freezes
the heap again; the share of churn that pays for a re-evaluation is set
to nothing, so the node's own housekeeping pass runs one. Neither is
an option of the program.
In the measured window a client of the repo's own subscribes to
filters the flood's topics match, a full collection freezes the heap
(freeze 1), the client unsubscribes half, drops, reconnects and
subscribes again, the housekeeping pass re-evaluates, and a second
full collection freezes what came since (freeze 2). The harness's
comparison then holds every delivery of the flood exact, and one
`GCPOLICY {...}` line on stderr says what the policy did.
"""

import asyncio
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as R                       # noqa: E402
from emqx_tpu.broker import trace as T               # noqa: E402

T.FREEZE_PAUSE_FLOOR_S = -1.0
T.REEVALUATE_CHURN_SHARE = 1e-9

REPORT: dict = {}
_set_up, _window = R.Run.set_up, R.Run.window


async def set_up(self, port):
    self.port = port
    await _set_up(self, port)


async def _drained(client) -> int:
    """Messages the client has got, once a short wait brings no more."""
    await asyncio.sleep(0.3)
    n = 0
    while not client.messages.empty():
        client.messages.get_nowait()
        n += 1
    return n


async def _full_collection(node) -> int:
    """Two generation-2 collections (the first may be the base), then
    the loop's next turns: the policy's freeze. Returns freezes so
    far."""
    gc.collect()
    gc.collect()
    await asyncio.sleep(0.05)
    return node.metrics.val("runtime.gc.freezes")


async def churn(run) -> None:
    from emqx_tpu.client import Client
    node = run.node
    filters = list(run.pop.filters())
    mine = filters[:: max(1, len(filters) // 16)][:16]
    await asyncio.sleep(float(run.cell.traffic["lead_in_s"]) + 0.5)
    c = Client(port=run.port, clientid="gc-churn")
    await c.connect()
    for f in mine:
        await c.subscribe(f)
    first = await _full_collection(node)
    got1 = await _drained(c)
    await c.unsubscribe(mine[:8])
    await c.disconnect()
    c = Client(port=run.port, clientid="gc-churn")
    await c.connect()
    for f in mine[4:]:
        await c.subscribe(f)
    node.sweep()            # the housekeeping pass: churn -> re-evaluate
    second = await _full_collection(node)
    got2 = await _drained(c)
    await c.disconnect()
    REPORT.update(
        freezes=[first, second],
        reevaluations=node.metrics.val("runtime.gc.reevaluations"),
        frozen_objects=node.stats.sample()["runtime.gc.frozen_objects"],
        churn_received=[got1, got2])


async def window(self):
    task = asyncio.ensure_future(churn(self))
    await _window(self)
    await task
    print("GCPOLICY " + json.dumps(REPORT), file=sys.stderr, flush=True)


R.Run.set_up, R.Run.window = set_up, window

if __name__ == "__main__":
    R.leave(R.main())
