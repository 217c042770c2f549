"""Load from one process: streaming chat requests over HTTP, open or closed loop.

Open loop: arrivals on a schedule fixed before the window, whatever the
server does; each request is timed from when it was DUE, so a stall counts
against every request it delays, and how late the generator itself ran is
reported. Closed loop: a stated number of clients, each sending its next
request when its last ended, timed from when it was sent.

A cell's schedule is data: the multiset of inter-arrival gaps, prompt lengths
and answer lengths (the quantiles of their distributions) is the same in every
run, so every seed offers the same work; `--seed` draws their order and the
words of the prompts.
Measured (PR 24, 30 s windows of 27 requests behind a router that admits 8
streams): two runs of one order agreed to 0.1 % in tokens/s and ~5 % in the
90th percentile of time to first token; six orders spread over 83-95 tokens/s
and 114-1,482 ms, because whether more than 8 streams meet is the order's
doing. A tail that is to be steady over orders needs the hundred requests a
window that ISSUE 24 asked for, or a router that does not queue below the knee.
"""
import http.client
import json
import math
import statistics
import threading
import time

import numpy as np


def _quantiles_log_uniform(lo: int, hi: int, n: int) -> list:
    return [int(round(math.exp(math.log(lo) + (math.log(hi) - math.log(lo)) * (i + 0.5) / n)))
            for i in range(n)]


def open_schedule(order_seed: int, seconds: float, rate: float, prompt_tokens,
                  max_tokens) -> list:
    """[{due_s, prompt_len, max_tokens}]: n = rate x seconds arrivals whose gaps
    are the n quantiles of the exponential distribution (a Poisson process's
    gaps); lengths the quantiles of log-uniform ranges; all three shuffled by
    `order_seed`."""
    n = max(1, int(round(rate * seconds)))
    rng = np.random.default_rng([order_seed, 10])
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)])
    gaps *= (seconds / n) / gaps.mean()  # the quantile grid clips the tail: restore the mean
    rng.shuffle(gaps)
    due = np.cumsum(gaps) - gaps[0]
    plen = np.array(_quantiles_log_uniform(*prompt_tokens, n))
    mtok = np.array(_quantiles_log_uniform(*max_tokens, n))
    rng.shuffle(plen)
    rng.shuffle(mtok)
    return [{"due_s": float(d), "prompt_len": int(p), "max_tokens": int(m)}
            for d, p, m in zip(due, plen, mtok)]


class Client:
    """Sends streaming chat requests and records what the client sees."""

    def __init__(self, host: str, port: int, path: str, model: str, make_prompt):
        self.host, self.port, self.path, self.model = host, port, path, model
        self.make_prompt = make_prompt  # (n words) -> str; called under the lock
        self.records = []
        self._lock = threading.Lock()
        self.stop_reading = threading.Event()  # closed loop: the window ended

    def body(self, prompt_len: int, max_tokens: int, stream: bool = True) -> dict:
        with self._lock:
            content = self.make_prompt(prompt_len)
        return {"model": self.model, "stream": stream, "max_tokens": max_tokens,
                "temperature": 0.0, "messages": [{"role": "user", "content": content}]}

    def request(self, body: dict, due: float = None, timeout: float = 300.0) -> dict:
        """One streaming request. Times are time.perf_counter(). A content
        frame is a frame whose delta carries text; the role frame is free."""
        rec = {"due": due, "sent": time.perf_counter(), "frames": [], "words": 0,
               "max_tokens": body["max_tokens"], "finish": None, "error": None, "cut": False}
        if due is None:
            rec["due"] = rec["sent"]
        conn = http.client.HTTPConnection(self.host, self.port, timeout=timeout)
        try:
            conn.request("POST", self.path, body=json.dumps(body).encode(),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["error"] = f"http {resp.status}: {resp.read(200)!r}"
                return rec
            while True:
                if self.stop_reading.is_set():
                    rec["cut"] = True  # hanging up makes the server abort the request
                    break
                line = resp.readline()
                if not line:
                    break
                if not line.startswith(b"data: "):
                    if line.startswith(b"error:"):
                        rec["error"] = line.decode(errors="replace").strip()
                    continue
                payload = line[6:].strip()
                if payload == b"[DONE]":
                    continue  # read on to the end of the body: the server closes it
                choice = json.loads(payload)["choices"][0]
                text = choice["delta"].get("content") or ""
                if text:
                    words = len(text.split())
                    rec["frames"].append((time.perf_counter(), words))
                    rec["words"] += words
                if choice.get("finish_reason"):
                    rec["finish"] = choice["finish_reason"]
        except Exception as e:  # noqa: BLE001 - a failed request is a result, not a crash
            rec["error"] = repr(e)
        finally:
            conn.close()
            rec["end"] = time.perf_counter()
            with self._lock:
                self.records.append(rec)
        return rec

    def unary(self, body: dict, timeout: float = 300.0) -> dict:
        conn = http.client.HTTPConnection(self.host, self.port, timeout=timeout)
        try:
            conn.request("POST", self.path, body=json.dumps(dict(body, stream=False)).encode(),
                         headers={"Content-Type": "application/json"})
            return json.loads(conn.getresponse().read())
        finally:
            conn.close()


def run_open(client: Client, schedule: list, seconds: float, drain_s: float) -> dict:
    """Fire the schedule; wait up to drain_s after the window for stragglers."""
    threads = []
    w0 = time.perf_counter()
    for item in schedule:
        due = w0 + item["due_s"]
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        body = client.body(item["prompt_len"], item["max_tokens"])
        t = threading.Thread(target=client.request, args=(body, due), daemon=True)
        t.start()
        threads.append(t)
    rest = w0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    w1 = time.perf_counter()
    deadline = w1 + drain_s
    for t in threads:
        t.join(max(0.0, deadline - time.perf_counter()))
    unfinished = sum(t.is_alive() for t in threads)
    return {"w0": w0, "w1": w1, "attempted": len(schedule), "unfinished": unfinished,
            "drain_s": time.perf_counter() - w1}


def run_closed(client: Client, clients: int, seconds: float, prompt_len: int,
               max_tokens: int) -> dict:
    """`clients` threads, each sending its next request when its last ended.
    At the end of the window every client hangs up: the requests then in
    flight keep the latencies they have shown so far and are marked `cut`."""
    w0 = time.perf_counter()
    end = w0 + seconds

    def loop():
        while time.perf_counter() < end:
            client.request(client.body(prompt_len, max_tokens))

    threads = [threading.Thread(target=loop, daemon=True) for _ in range(clients)]
    for t in threads:
        t.start()
    time.sleep(max(0.0, end - time.perf_counter()))
    w1 = time.perf_counter()
    client.stop_reading.set()
    for t in threads:
        t.join(60.0)
    unfinished = sum(t.is_alive() for t in threads)
    client.stop_reading.clear()
    return {"w0": w0, "w1": w1, "attempted": len(client.records) + unfinished,
            "unfinished": unfinished, "drain_s": time.perf_counter() - w1}


def p90(values: list):
    if len(values) < 2:
        return values[0] if values else None
    return statistics.quantiles(values, n=10, method="inclusive")[-1]


def summarize(records: list, run: dict) -> dict:
    """What the client saw, over all requests of the window."""
    w0, w1 = run["w0"], run["w1"]
    ok = [r for r in records if r["error"] is None]
    tokens_in_window = sum(n for r in ok for t, n in r["frames"] if w0 <= t <= w1)
    # over the requests that got a token. One that had none when the window ended
    # is counted as `starved` and enters no percentile: the time it had waited by
    # then is set by the window's length, not by the system
    ttft = [r["frames"][0][0] - r["due"] for r in ok if r["frames"]]
    tpot = [(r["frames"][-1][0] - r["frames"][0][0]) / (r["words"] - r["frames"][0][1])
            for r in ok if r["frames"] and r["words"] > r["frames"][0][1]]
    late = [r["sent"] - r["due"] for r in records]
    complete = [r for r in ok if not r["cut"]]
    # a completed request returned max_tokens tokens, or stopped at end-of-sequence
    # (whose token the decoded text leaves out)
    short = [r for r in complete if not (
        r["words"] == r["max_tokens"] or (r["finish"] == "stop" and r["words"] < r["max_tokens"]))]
    no_first_token = sum(1 for r in ok if not r["frames"] and not r["cut"])
    failed = len(records) - len(ok) + run["unfinished"] + no_first_token
    return {
        "window_s": w1 - w0, "attempted": run["attempted"], "failed": failed,
        "completed": len(complete), "cut_at_window_end": sum(r["cut"] for r in ok),
        "tokens_in_window": tokens_in_window,
        "tokens_per_s": tokens_in_window / (w1 - w0),
        "ttft_samples": len(ttft), "tpot_samples": len(tpot),
        "starved": sum(1 for r in ok if not r["frames"]) + run["unfinished"],
        "ttft_p90_ms": None if not ttft else 1e3 * p90(ttft),
        "ttft_p50_ms": None if not ttft else 1e3 * statistics.median(ttft),
        "tpot_p90_ms": None if not tpot else 1e3 * p90(tpot),
        "tpot_p50_ms": None if not tpot else 1e3 * statistics.median(tpot),
        "generator_late_max_ms": 1e3 * max(late) if late else 0.0,
        "generator_late_p90_ms": 1e3 * p90(late) if late else 0.0,
        "wrong_length": len(short), "drain_s": run["drain_s"],
        "errors": [r["error"] for r in records if r["error"]][:3],
    }
