"""The wall-clock driver holds each replica's time to the wall: the idle
jumps ``ServeEngine._tick`` makes (``now += keep_alive / 8`` with only warm
containers left) never reach ``_recycle_idle``, so no warm container
expires before its keep-alive has passed on the wall clock."""
import types

from bench import driver as D


class IdleReplica:
    """Holds one warm container and ticks like an idle engine: jump, then
    recycle."""

    def __init__(self):
        self.replica_id = "A"
        self.cfg = types.SimpleNamespace(vocab_size=8)
        self._decode_jit = {}
        self.pending, self.active, self.events = [], {}, []
        self.warm = {"f": [(0.0, "r0", 0)]}
        self.now = 0.0
        self.seen = []                    # (engine time, wall) at recycle

    def host_work(self):
        return False

    def _recycle_idle(self):
        self.seen.append((self.now, self.wall()))

    def _tick(self, todo):
        self.now += 4.0 / 8
        self._recycle_idle()


def test_idle_jumps_never_reach_recycle():
    eng = IdleReplica()
    drv = D.Driver({"A": eng}, lambda req: "A")
    eng.wall = drv.wall
    drv.run([], 0.05, profiles={})
    assert len(eng.seen) > 3
    assert all(now <= wall for now, wall in eng.seen)
