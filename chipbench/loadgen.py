"""Open-loop load for a serving cell: the schedule, and its execution.

Copied in spirit from fedml_tpu/soak/loadgen.py (seeded schedule, open loop
from a thread pool, lognormal lengths, SSE first-token and token-gap
timing), with what a benchmark needs changed: every request is timed from
the moment it was DUE, and the MIX fixes the schedule: its lengths and
arrival gaps are the distributions' evenly spaced quantiles, put in order by
the mix's own `schedule_seed`. `--seed` draws the prompts' token ids (and,
in the driver, the weights) and nothing else. On the chip the order alone
moved the first-token tail by 40% between orders (which request queues
behind which), while two runs of one order agree to 0.2% (PERF.md section
6): an order drawn from `--seed` would bury any change under that.

The schedule is a pure function of (mix, seconds, seed).
"""
from __future__ import annotations

import dataclasses
import http.client
import json
import math
import statistics
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass(frozen=True)
class Planned:
    due: float              # seconds from the start of the window
    tokens: tuple           # the prompt
    max_new: int


@dataclasses.dataclass
class Served:
    plan: Planned
    sent: float = math.nan          # when the generator got to it
    status: int = 0                 # HTTP status; 599 = connection failure
    token_times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False              # the stream ended with its `done` frame
    error: str = ""

    @property
    def ok(self) -> bool:
        return (self.status == 200 and self.done
                and len(self.tokens) == self.plan.max_new)


def _lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at the lognormal's evenly spaced quantiles, clipped: the
    same set whatever the seed."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(float(v)) for v in u])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.round(raw), spec["min"], spec["max"]).astype(int)


def build_schedule(mix: dict, seconds: float, seed: int, vocab: int) -> list:
    """Poisson arrivals at mix["rate_rps"] for `seconds`: the exponential's
    evenly spaced quantiles as gaps, the lognormals' as lengths, each
    shuffled by the mix's `schedule_seed`; prompts are random ids in
    [1, vocab) drawn from `seed`."""
    n = max(1, round(mix["rate_rps"] * seconds))
    order = np.random.RandomState(mix["schedule_seed"])
    rs = np.random.RandomState(seed % 2 ** 32)
    u = (np.arange(n) + 0.5) / n
    gaps = order.permutation(-np.log1p(-u) / mix["rate_rps"])
    prompts = order.permutation(_lengths(mix["prompt"], n))
    outputs = order.permutation(_lengths(mix["output"], n))
    due = np.cumsum(gaps) - gaps[0] * 0.5
    out = []
    for t, p, o in zip(due, prompts, outputs):
        if t >= seconds:
            break
        out.append(Planned(float(t), tuple(
            int(v) for v in rs.randint(1, vocab, int(p))), int(o)))
    return out


class OpenLoop:
    """Sends each planned request at its due time from a pool of threads,
    without waiting for earlier ones; streams the answer over SSE and keeps
    when every token came."""

    def __init__(self, host: str, port: int, schedule: list,
                 workers: int = 96, timeout_s: float = 120.0):
        self.host, self.port = host, port
        self.schedule = schedule
        self.timeout_s = timeout_s
        self.rows = [Served(p) for p in schedule]
        self._pool = ThreadPoolExecutor(max_workers=workers)
        self._futures: list = []
        self._thread = threading.Thread(target=self._dispatch, daemon=True)
        self.t0 = 0.0

    def start(self) -> None:
        self.t0 = time.perf_counter()
        self._thread.start()

    def _dispatch(self) -> None:
        for row in self.rows:
            delay = row.plan.due - (time.perf_counter() - self.t0)
            if delay > 0:
                time.sleep(delay)
            self._futures.append(self._pool.submit(self._issue, row))

    def drain(self, until_s: float) -> None:
        """Wait for every request sent, at most until `until_s` seconds
        from the start; a stream still open then stays not-done."""
        self._thread.join(timeout=max(0.0, until_s - self.now()))
        for f in self._futures:
            try:
                f.result(timeout=max(0.05, until_s - self.now()))
            except Exception:  # noqa: BLE001 — a late row simply is not done
                pass
        self._pool.shutdown(wait=False, cancel_futures=True)

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def _issue(self, row: Served) -> None:
        row.sent = self.now()
        body = json.dumps({"tokens": list(row.plan.tokens),
                           "max_new_tokens": row.plan.max_new,
                           "stream": True})
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=self.timeout_s)
        try:
            conn.request("POST", "/predict", body,
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            row.status = resp.status
            if resp.status != 200:
                row.error = resp.read(300).decode("utf-8", "replace")
                return
            for raw in resp:
                line = raw.strip()
                if not line.startswith(b"data:"):
                    continue
                ev = json.loads(line[5:])
                if "token" in ev:
                    row.token_times.append(self.now())
                    row.tokens.append(int(ev["token"]))
                elif ev.get("done"):
                    row.done = list(ev["generated_tokens"]) == row.tokens
                    if not row.done:
                        row.error = "done frame differs from the stream"
                    break
                elif "error" in ev:
                    row.status = int(ev.get("code", 503))
                    row.error = str(ev["error"])[:300]
                    break
        except (OSError, http.client.HTTPException, ValueError) as e:
            row.status = row.status if row.status not in (0, 200) else 599
            row.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            conn.close()


def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest rank above: of all the values."""
    v = sorted(values)
    return v[min(len(v) - 1, max(0, math.ceil(q / 100.0 * len(v)) - 1))]
