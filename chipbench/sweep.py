"""Several offered rates in one process after one set-up: where is the knee?

    python3 -m chipbench.sweep --workload serve-chat-steady --rates 2.0,2.5,3.1 --seconds 30

Never run by the driver. It is how a serving cell's fixed rate is found,
once, on the chip: the cell's traffic is offered at each rate in turn on one
engine (after the cell's ramp at the first rate), and one line per rate says
how many requests waited for a row in each third of its window. A rate is
clean if none waited in the last third; a cell below the knee runs at four
fifths of the highest clean rate. ``--rehearse`` as in ``run.py``.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()

import argparse
import sys

from . import run as _run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="requests per second, comma separated, rising")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    ctx, device = _run.open_cell(args.workload, args.seed, args.seconds,
                                 False, args.rehearse, _T_PROCESS)
    if ctx is None:
        return device
    if ctx.cell["driver"] != "serve":
        raise SystemExit("a sweep is over a serving cell's rate")
    from .drivers import serve
    from .drivers.common import Heartbeat
    ses = serve.Session(ctx)
    rates = [float(r) for r in args.rates.split(",")]
    base = ses.cell["traffic"]
    before = [ses.phase({**base, "rate_per_s": rates[0]},
                        float(base.get("ramp_s", 0)), phase_seed=2)]
    for k, rate in enumerate(rates):
        heart = Heartbeat().start()
        win = ses.phase({**base, "rate_per_s": rate}, args.seconds,
                        phase_seed=3 + k)
        stops = heart.stop()
        notes, _, _ = serve.report(ses, before, win)
        ctx.emit("rate", device=device, **notes, **stops)
        before.append(win)
    ses.release_engine()
    ctx.emit("check", **ses.judge())
    return 0


if __name__ == "__main__":
    sys.exit(main())
