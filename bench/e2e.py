"""End-to-end arithmetic: what a client of the serving path sees.

Every statistic is over the whole window: a tail is the tail of every
request or gap in it, a rate is all the work over all the time.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

UNITS = {"ttft_p90_ms": "ms", "tpot_p90_ms": "ms", "tokens_per_s": "tokens/s",
         "setup_s": "s"}


def _pct(x: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q, method="linear"))


def metrics(requests: List[Dict], t_open: float, t_close: float,
            setup_s: float) -> Dict[str, float]:
    """``requests`` carry ``t_send``, ``t_first`` and ``t_tokens`` (the
    host time each generated token was read back); times in seconds on
    one clock.

    ttft: send -> first token, over requests whose first token falls in
    the window; tpot: every gap between consecutive tokens of one
    request whose later token falls in the window; tokens_per_s: tokens
    read back in the window over its length.
    """
    ttft, gaps, tokens = [], [], 0
    for r in requests:
        ts = r["t_tokens"]
        if ts and t_open <= ts[0] <= t_close:
            ttft.append(ts[0] - r["t_send"])
        for a, b in zip(ts, ts[1:]):
            if t_open <= b <= t_close:
                gaps.append(b - a)
        tokens += sum(1 for t in ts if t_open <= t <= t_close)
    if not ttft or not gaps:
        raise RuntimeError(f"the window saw {len(ttft)} first tokens and "
                           f"{len(gaps)} token gaps; nothing to report")
    return {"ttft_p90_ms": 1e3 * _pct(ttft, 90),
            "tpot_p90_ms": 1e3 * _pct(gaps, 90),
            "tokens_per_s": tokens / (t_close - t_open),
            "setup_s": setup_s}


def counts(requests: List[Dict], t_open: float, t_close: float) -> Dict[str, int]:
    """Sample sizes behind the tails."""
    first = sum(1 for r in requests
                if r["t_tokens"] and t_open <= r["t_tokens"][0] <= t_close)
    done = sum(1 for r in requests
               if r.get("t_done") is not None and t_open <= r["t_done"] <= t_close)
    gaps = sum(1 for r in requests for b in r["t_tokens"][1:]
               if t_open <= b <= t_close)
    return {"first_tokens": first, "completed": done, "token_gaps": gaps}


def percentiles(requests: List[Dict], t_open: float, t_close: float) -> Dict:
    """Quartiles and tails of the window's TTFTs and token gaps, in ms
    (stderr diagnostics beside the metrics)."""
    ttft = [r["t_tokens"][0] - r["t_send"] for r in requests
            if r["t_tokens"] and t_open <= r["t_tokens"][0] <= t_close]
    gaps = [b - a for r in requests for a, b in zip(r["t_tokens"], r["t_tokens"][1:])
            if t_open <= b <= t_close]
    return {name: {f"p{q}": 1e3 * _pct(x, q) for q in (25, 50, 75, 90, 95, 99)}
            for name, x in (("ttft_ms", ttft), ("tpot_ms", gaps)) if x}
