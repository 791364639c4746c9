"""What every generator kind shares: the log of every exchange of
every client thread (stats.Req by kind), and the closed loop of client
threads itself."""

from __future__ import annotations

import threading
import time

from . import stats

MIB = float(1 << 20)


def ms(seconds):
    return None if seconds is None else seconds * 1e3


class RequestLog:
    """Every exchange of every client thread, as stats.Req by kind."""

    def __init__(self):
        self._t0 = time.monotonic()
        self._lock = threading.Lock()
        self._reqs: dict[str, list[stats.Req]] = {}
        self._failures: list[str] = []
        self.completed: list[dict] = []  # objects uploaded whole

    def record(self, kind: str, reply, nbytes: int, ok: bool) -> None:
        now = time.monotonic()
        if isinstance(reply, BaseException):
            req = stats.Req(now, now, nbytes, False)
            why = f"{kind}: {type(reply).__name__}: {reply}"
        else:
            req = stats.Req(reply.t_send, reply.t_last, nbytes, ok,
                            reply.t_first)
            why = (f"{kind}: HTTP {reply.status} "
                   f"{bytes(reply.body[:200])!r}" if reply.status >= 300
                   else f"{kind}: HTTP {reply.status}, wrong content")
        with self._lock:
            self._reqs.setdefault(kind, []).append(req)
            if not ok:
                self._failures.append(
                    f"+{now - self._t0:.1f}s after the generator was made: "
                    + why[:400])

    def complete(self, obj: dict) -> None:
        with self._lock:
            self.completed.append(obj)

    def of(self, kind: str) -> list[stats.Req]:
        with self._lock:
            return list(self._reqs.get(kind, []))

    def failures(self) -> list[str]:
        with self._lock:
            return list(self._failures)

    def counts(self, w0: float, w1: float) -> dict:
        """Requests that ended inside the window or were in flight at
        its end, and how many of them failed."""
        with self._lock:
            reqs = [r for rs in self._reqs.values() for r in rs]
        seen = [r for r in reqs if r.t_end >= w0 and r.t_start < w1]
        return {"attempted": len(seen),
                "failed": sum(1 for r in seen if not r.ok)}


class ClosedLoop:
    """`clients` threads, each running `client_loop(i)` on a connection
    of its own until `stopping()`: callers that each wait for a reply.
    A generator kind derives from it, names its primary request's kind
    in `PRIMARY` and logs every exchange in `self.log`."""

    PRIMARY = ""

    def __init__(self, env):
        self.env, self.clients = env, int(env.params["clients"])
        self.log = RequestLog()
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []

    def client_loop(self, i: int, client) -> None:
        raise NotImplementedError

    def stopping(self) -> bool:
        return self._stop.is_set()

    def _run(self, i: int) -> None:
        client = self.env.client()
        try:
            self.client_loop(i, client)
        finally:
            client.close()

    def start(self) -> None:
        self._threads = [threading.Thread(target=self._run, args=(i,),
                                          daemon=True, name=f"client{i}")
                         for i in range(self.clients)]
        for t in self._threads:
            t.start()

    def warm(self) -> bool:
        """Every client has had a primary request answered."""
        return len(self.log.of(self.PRIMARY)) >= self.clients

    def stop(self) -> None:
        """Each client lets the request it has in flight finish."""
        self._stop.set()
        for t in self._threads:
            t.join(timeout=300)
            if t.is_alive():
                raise RuntimeError(f"{t.name} did not finish in 300 s")
