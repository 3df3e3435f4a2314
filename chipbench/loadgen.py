"""The load generator: an open loop against ``POST /generate``.

Two halves. :func:`make_schedule` and :func:`summarize` are the
yardstick (arrivals, lengths and token ids from a traffic file and
``--seed``; records to metrics) and run in the harness. ``python3
chipbench/loadgen.py <schedule.json> <records.json>`` is the generator
itself: one process, one thread, the standard library only, so that it
neither holds a chip nor shares the engine thread's interpreter lock.

A traffic file fixes the WORK: its own ``set_seed`` draws one cycle of
requests (prompt length, output length, gap to the next), as many as the
rate offers in the window. ``--seed`` draws where in the cycle the window
STARTS, and the token ids: every seed replays the same cycle from
another point, so the same bursts and the same long prompts meet the
same neighbours, and a tail read in one run is there in the next. (A
fresh order for every seed moved the 95th percentiles by more than any
change to the program would.)
"""

import asyncio
import json
import sys
import time


def _draw(rng, dist, n):
    """`n` draws of a distribution a traffic file names."""
    import numpy as np

    kind = dist["dist"]
    if kind == "lognormal":
        x = rng.lognormal(np.log(dist["median"]), dist["sigma"], n)
    elif kind == "exponential":      # Poisson arrivals
        x = rng.exponential(dist["mean"], n)
    elif kind == "gamma":            # bursty arrivals: cv > 1
        shape = 1.0 / dist["cv"] ** 2
        x = rng.gamma(shape, dist["mean"] / shape, n)
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "clip" in dist:
        x = np.clip(x, *dist["clip"])
    return x


def make_schedule(traffic, seed, seconds, vocab_size):
    """The requests of one window: ``[{due, tokens, max_new}, ...]``,
    `due` in seconds from the window's start and inside it."""
    import numpy as np

    n = max(1, round(traffic["rate_per_s"] * seconds))
    fixed = np.random.default_rng(traffic["set_seed"])
    prompts = np.rint(_draw(fixed, traffic["prompt_tokens"], n)).astype(int)
    outputs = np.rint(_draw(fixed, traffic["output_tokens"], n)).astype(int)
    gaps = _draw(fixed, {**traffic["arrivals"],
                         "mean": 1.0 / traffic["rate_per_s"]}, n)
    order = np.random.default_rng(seed)
    start = int(order.integers(n))
    prompts, outputs, gaps = (np.roll(x, -start)
                              for x in (prompts, outputs, gaps))
    # gaps[i] follows request i. The cycle's own sum fixes the scale:
    # the first request is due at 0, the last one gap before the end
    due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
    prefix = traffic.get("shared_prefix")
    prefixes = [order.integers(1, vocab_size, prefix["tokens"])
                for _ in range(prefix["count"])] if prefix else []
    requests = []
    for i in range(n):
        tokens = order.integers(1, vocab_size, prompts[i])
        if prefixes:
            head = prefixes[order.integers(len(prefixes))]
            tokens = np.concatenate([head, tokens])
        requests.append({"due": float(due[i]),
                         "tokens": [int(t) for t in tokens],
                         "max_new": int(outputs[i])})
    return requests


def percentile(values, q):
    """Linear-interpolated percentile of a non-empty list."""
    xs = sorted(values)
    at = (len(xs) - 1) * q / 100.0
    lo = int(at)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (at - lo)


def summarize(records, seconds, deadline_s):
    """Records of one window to what its users saw. A request is
    complete when its last token has arrived; one that failed, or was
    not complete `deadline_s` after the window's start, misses: its time
    to first token counts as the time to that deadline."""
    failed = [r for r in records
              if r["error"] or len(r["token_at"]) < r["max_new"]]
    ttft, tpot, late = [], [], []
    delivered = 0
    for r in records:
        at = r["token_at"]
        ok = not r["error"] and len(at) >= r["max_new"]
        ttft.append(at[0] - r["due"] if ok else deadline_s - r["due"])
        late.append(r["sent"] - r["due"] if r["sent"] is not None
                    else deadline_s - r["due"])
        if ok and len(at) > 1:
            tpot.append((at[-1] - at[0]) / (len(at) - 1))
        delivered += sum(1 for t in at if 0 <= t <= seconds)
    return {
        "attempted": len(records), "failed": len(failed),
        "ttft_mean_ms": 1e3 * sum(ttft) / len(ttft),
        "ttft_p50_ms": 1e3 * percentile(ttft, 50),
        "ttft_p95_ms": 1e3 * percentile(ttft, 95),
        "tpot_p50_ms": 1e3 * percentile(tpot, 50) if tpot else None,
        "tpot_p95_ms": 1e3 * percentile(tpot, 95) if tpot else None,
        "serve_out_tokens_per_s": delivered / seconds,
        "late_p95_ms": 1e3 * percentile(late, 95),
        "backlog_mid": _in_flight(records, seconds / 2),
        "backlog_end": _in_flight(records, seconds),
    }


def _in_flight(records, t):
    """Requests due by `t` (seconds from the window's start) that were
    not complete then."""
    return sum(1 for r in records if r["due"] <= t and not (
        len(r["token_at"]) >= r["max_new"] and r["token_at"][-1] <= t))


# -- the generator process ---------------------------------------------------


async def _one(host, port, request, start_at, record):
    await asyncio.sleep(max(0.0, start_at + request["due"] - time.time()))
    body = json.dumps({"tokens": request["tokens"],
                       "max_new_tokens": request["max_new"],
                       "stream": True}).encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(
            b"POST /generate HTTP/1.1\r\nHost: bench\r\n"
            b"Content-Type: application/json\r\nContent-Length: "
            + str(len(body)).encode() + b"\r\n\r\n" + body)
        record["sent"] = time.time() - start_at
        await writer.drain()
        status = await reader.readline()
        if b" 200 " not in status:
            record["error"] = status.decode("latin-1").strip()
            return
        while True:                     # to the stream's end, or its tail
            line = await reader.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            event = json.loads(line[6:])
            if "token" in event:
                record["token_at"].append(time.time() - start_at)
            elif "error" in event:
                record["error"] = str(event["error"])
                break
            else:
                break
    except (OSError, ValueError) as e:
        record["error"] = f"{type(e).__name__}: {e}"
    finally:
        if writer is not None:
            writer.close()


async def _generate(schedule):
    host, port = schedule["address"]
    start_at = schedule["start_at"]
    records = [{"due": r["due"], "max_new": r["max_new"], "sent": None,
                "token_at": [], "error": None}
               for r in schedule["requests"]]
    tasks = [asyncio.ensure_future(_one(host, port, r, start_at, rec))
             for r, rec in zip(schedule["requests"], records)]
    _, pending = await asyncio.wait(
        tasks, timeout=max(0.0, start_at + schedule["deadline_s"]
                           - time.time()))
    for task in pending:
        task.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    return records


def main(argv):
    with open(argv[1]) as f:
        schedule = json.load(f)
    records = asyncio.run(_generate(schedule))
    with open(argv[2], "w") as f:
        json.dump(records, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
