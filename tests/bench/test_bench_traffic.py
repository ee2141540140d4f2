"""The open-loop generator and serving loop of bench/traffic.py."""
import numpy as np

import conftest  # noqa: F401  (puts the checkout on sys.path)

from bench import traffic

MIX = {"rate_per_s": 10.0, "set_seed": 0,
       "prompt": {"dist": "lognormal", "median": 512, "sigma": 0.8,
                  "min": 64, "max": 2048},
       "output": {"dist": "uniform", "min": 16, "max": 64}}


def test_same_seed_same_requests():
    a = traffic.schedule(MIX, 2 ** 31 + 7, 20.0, 1000)
    b = traffic.schedule(MIX, 2 ** 31 + 7, 20.0, 1000)
    assert [(r.due_s, r.prompt, r.max_new) for r in a] == \
        [(r.due_s, r.prompt, r.max_new) for r in b]


def test_every_seed_offers_the_same_work_at_the_same_times():
    a = traffic.schedule(MIX, 1, 20.0, 1000)
    b = traffic.schedule(MIX, 2 ** 33 + 2, 20.0, 1000)
    assert len(a) == len(b) == 200
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert len({len(r.prompt) for r in a}) > 50
    assert all(0 <= r.due_s < 20.0 for r in a)


def test_lengths_stay_in_their_bounds():
    rng = np.random.default_rng(0)
    x = traffic._lengths({"dist": "loguniform", "min": 1024, "max": 4096},
                         1000, rng)
    assert x.min() >= 1024 and x.max() <= 4096


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-4)


class StallServer:
    """One slot pool; every call advances the fake clock by a fixed step,
    and one decode step stalls for ``stall`` seconds."""

    def __init__(self, clock, stall_at, stall):
        self.clock, self.stall_at, self.stall = clock, stall_at, stall
        self.waiting = []
        self.active = np.zeros(4, bool)
        self.pos = np.zeros(4, np.int64)
        self.prompt_len = np.zeros(4, np.int64)
        self.left = {}
        self.slot_of = {}
        self.n = 0
        self.admit_t = {}

    def submit(self, prompt, max_new):
        rid = self.n
        self.n += 1
        self.waiting.append((rid, len(prompt), max_new))
        return rid

    def admit_waiting(self):
        evs = []
        while self.waiting and not self.active.all():
            rid, plen, max_new = self.waiting.pop(0)
            slot = int(np.argmin(self.active))
            self.admit_t[rid] = self.clock()
            self.clock.t += 0.001
            self.active[slot] = True
            self.pos[slot] = plen
            self.prompt_len[slot] = plen
            self.slot_of[slot] = rid
            self.left[rid] = max_new - 1
            evs += [("admit", rid, slot), ("token", rid, 0)]
        return evs

    def decode_once(self):
        if not self.active.any():
            return []
        before = self.clock.t
        self.clock.t += 0.01
        if before < self.stall_at <= self.clock.t:
            self.clock.t += self.stall
        evs = []
        for slot in np.nonzero(self.active)[0]:
            rid = self.slot_of[int(slot)]
            self.pos[slot] += 1
            evs.append(("token", rid, 1))
            self.left[rid] -= 1
            if self.left[rid] <= 0:
                self.active[slot] = False
                evs.append(("retire", rid, "length"))
        return evs


def _reqs(dues, max_new=5):
    return [traffic.Request(d, [1, 2, 3], max_new) for d in dues]


def test_a_stall_is_charged_to_the_requests_queued_behind_it():
    clock = FakeClock()
    srv = StallServer(clock, stall_at=1.0, stall=0.5)
    dues = [0.2, 0.995, 1.1, 1.2, 1.3, 2.5]
    served = traffic.drive(srv, _reqs(dues), 3.0, clock=clock,
                           sleep=clock.sleep)
    ttft = dict(zip(sorted(served.due, key=served.due.get),
                    [None] * len(dues)))
    for rid in served.due:
        ttft[rid] = served.tokens[rid][0] - served.due[rid]
    by_due = [ttft[r] for r in sorted(served.due, key=served.due.get)]
    # due during the stall: each waits for its end (1.0 + 0.5 + step)
    for due, t in zip(dues[2:5], by_due[2:5]):
        assert t >= 1.5 - due - 1e-9
    # before and well after the stall: a step or two
    assert by_due[0] < 0.05 and by_due[-1] < 0.05
    # the queue wait runs from the due time too
    waits = sorted(served.queue_waits())
    assert waits[-1] >= 1.5 - 1.1 - 1e-9
    assert max(served.lateness()) >= 0.4   # submitted late, charged
    assert served.missing() == 0


def test_inter_token_gaps_stop_at_the_window():
    clock = FakeClock()
    srv = StallServer(clock, stall_at=99.0, stall=0.0)
    served = traffic.drive(srv, _reqs([0.0], max_new=500), 1.0,
                           clock=clock, sleep=clock.sleep)
    assert served.itls() and all(abs(g - 0.01) < 1e-9
                                 for g in served.itls())
    assert len(served.itls()) < 100
