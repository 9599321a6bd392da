"""A closed-loop client: calls its next op as soon as the last one
returned, with no think time."""

import threading
import time
import traceback


class Client(threading.Thread):
    """Records ``(op, arg, reply)`` of every call in program order, and
    its start and return on the host clock in ``t_ns``."""

    def __init__(self, bound, stream, go, stop, traffic):
        super().__init__(daemon=True)
        self.bound, self.stream, self.go, self.stop = bound, stream, go, stop
        self.done, self.t_ns, self.error = [], [], None

    def run(self):
        calls = {}
        done, t_ns = self.done, self.t_ns
        clock, stop = time.perf_counter_ns, self.stop
        try:
            self.go.wait()
            for op, arg in self.stream:
                if stop.is_set():
                    break
                f = calls.get(op) or calls.setdefault(
                    op, getattr(self.bound, op))
                t0 = clock()
                reply = f() if arg is None else f(arg)
                t1 = clock()
                done.append((op, arg, reply))
                t_ns.append((t0, t1))
        except BaseException:
            self.error = traceback.format_exc()
            stop.set()
