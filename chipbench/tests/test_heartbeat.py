"""The heartbeat: it reports a stop that the test makes and none in a quiet
second. The clock and the sleep are the test's own."""

from chipbench.drivers.common import Heartbeat


class Machine:
    """A clock that only ``sleep`` moves. ``stalls`` maps the number of a
    sleep to the seconds the machine stands still after it."""

    def __init__(self, heart_of, stalls, sleeps):
        self.now, self.n = 100.0, 0
        self.stalls, self.sleeps = stalls, sleeps
        self.heart_of = heart_of

    def clock(self):
        return self.now

    def sleep(self, seconds):
        self.n += 1
        self.now += seconds + self.stalls.get(self.n, 0.0)
        if self.n == self.sleeps:
            self.heart_of[0].done = True


def beat(stalls, sleeps=200):
    holder = []
    m = Machine(holder, stalls, sleeps)
    heart = Heartbeat(clock=m.clock, sleep=m.sleep)
    holder.append(heart)
    heart.beat()                      # in this thread: the thread's work
    return heart.stop()


def test_a_quiet_second_has_no_stop():
    notes = beat({})
    assert notes == {"stops_over_50ms": 0, "stop_longest_ms": 0.0,
                     "stop_sum_ms": 0.0, "heartbeats": 200}


def test_a_wake_up_200_ms_late_is_one_stop():
    notes = beat({70: 0.2, 90: 0.049})      # the second is under the limit
    assert notes["stops_over_50ms"] == 1 and notes["heartbeats"] == 200
    assert abs(notes["stop_longest_ms"] - 200.0) < 1e-6
    assert abs(notes["stop_sum_ms"] - 200.0) < 1e-6


def test_stops_add_up_and_the_longest_is_kept():
    notes = beat({3: 0.11, 50: 2.5, 120: 0.06})
    assert notes["stops_over_50ms"] == 3
    assert abs(notes["stop_longest_ms"] - 2500.0) < 1e-6
    assert abs(notes["stop_sum_ms"] - 2670.0) < 1e-6


def test_the_thread_starts_beats_and_ends():
    import time
    heart = Heartbeat().start()
    time.sleep(0.1)
    notes = heart.stop()
    assert notes["heartbeats"] >= 5 and not heart._thread.is_alive()
    assert Heartbeat().start(on=False).stop()["heartbeats"] == 0
