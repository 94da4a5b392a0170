"""strom-top — live per-class attribution + goodput console view.

Polls a running process's debug endpoint (obs/debugsrv.py, enabled by
``STROM_DEBUG_PORT`` in the serving/training process) and renders the
analysis layer as a terminal dashboard:

    STROM_DEBUG_PORT=9178 python serve.py &
    strom-top --port 9178            # live view, refresh every 2 s
    strom-top --port 9178 --once     # one frame (scripts, tests)

Top half: per-QoS-class critical-path attribution — where each class's
requests spend their wall time (p50/p99 per component plus the mean
share, ``/attrib``).  Bottom half: the goodput/waste ledger and
per-ring time-in-state (``/ledger``), plus ring breaker states
(``/health``).  Everything renders from the JSON the endpoint serves —
``strom-top`` holds no state and can attach/detach at any time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.error
import urllib.request

from nvme_strom_tpu.utils.stats import human_bytes as _human

#: component render order + compact labels (obs/attrib.py COMPONENTS)
_COMPONENTS = (
    ("sched_queue", "sched"),
    ("hostcache", "cache"),
    ("nvme_read", "nvme"),
    ("retry_backoff", "retry"),
    ("hedge", "hedge"),
    ("degraded", "degr"),
    ("bridge", "bridge"),
    ("ici_scatter", "ici"),
    ("prefill", "prefill"),
    ("unattributed", "other"),
)


def fetch(host: str, port: int, route: str, timeout: float = 2.0):
    url = f"http://{host}:{port}{route}"
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read().decode())


def render_frame(attrib: dict, ledger: dict, health: dict) -> str:
    """One dashboard frame from the three endpoint documents (pure —
    tests render canned documents)."""
    lines = []
    lines.append("strom-top — critical-path attribution "
                 "(per QoS class, µs)")
    if not attrib.get("enabled", True):
        lines.append("  attribution off — set STROM_ATTRIB=1 in the "
                     "serving process")
    else:
        classes = attrib.get("classes", {})
        if not classes:
            lines.append(f"  no retired requests yet "
                         f"(requests={attrib.get('requests', 0)})")
        hdr = f"  {'class':<10}{'n':>6}{'wall p50':>10}{'p99':>10}  "
        hdr += "".join(f"{lbl:>9}" for _c, lbl in _COMPONENTS)
        if classes:
            lines.append(hdr)
        for kl in sorted(classes):
            blk = classes[kl]
            row = (f"  {kl:<10}{blk['n']:>6}"
                   f"{blk['wall_p50_us']:>10}{blk['wall_p99_us']:>10}  ")
            comps = blk.get("components", {})
            # share of wall per component: the at-a-glance answer to
            # "where is this class's time going"
            row += "".join(
                f"{100.0 * comps.get(c, {}).get('share', 0.0):>8.1f}%"
                for c, _l in _COMPONENTS)
            lines.append(row)
        dropped = attrib.get("spans_dropped", 0)
        if dropped:
            lines.append(f"  ATTRIBUTION INCOMPLETE — {dropped} spans "
                         "dropped at the collector bound")
    lines.append("")
    lines.append("ledger — goodput vs waste")
    lines.append(f"  delivered {_human(ledger.get('delivered_bytes', 0)):>12}"
                 f"   goodput {_human(ledger.get('goodput_bytes', 0)):>12}"
                 f"   fraction {ledger.get('goodput_fraction', 1.0):.4f}")
    waste = ledger.get("waste", {})
    wrow = "   ".join(f"{k}={_human(v)}" for k, v in sorted(waste.items())
                      if v)
    lines.append(f"  waste     {_human(ledger.get('waste_bytes', 0)):>12}"
                 + (f"   ({wrow})" if wrow else ""))
    rs = ledger.get("ring_state_s")
    if rs:
        n = max((len(v) for v in rs.values()), default=0)
        for r in range(n):
            parts = []
            total = sum(rs[s][r] for s in rs if r < len(rs[s]))
            for state in ("busy", "idle", "stalled", "restarting"):
                vals = rs.get(state)
                if vals and r < len(vals) and total > 0:
                    parts.append(f"{state} {100.0 * vals[r] / total:.0f}%")
            lines.append(f"  ring {r}: " + "  ".join(parts))
    states = health.get("ring_health") or []
    if states:
        tag = " ".join(states)
        degraded = health.get("degraded")
        lines.append(f"  breakers: {tag}"
                     + ("   DEGRADED (buffered brown-out)"
                        if degraded else ""))
    phase = health.get("boot_phase")
    if phase and phase != "steady":
        # a replica mid-cold-start: worth a line until it reaches
        # steady, invisible afterwards (and for non-coldstart boots)
        lines.append(f"  boot: {phase} (cold start in progress — "
                     "serve-while-restoring)")
    drain = health.get("drain_phase")
    if drain and drain != "serving":
        # a replica mid-retirement: admissions defer while in-flight
        # work runs out, then the warm-state bundle ships (io/handoff)
        lines.append(f"  drain: {drain} (rolling replacement — "
                     "warm handoff in progress)")
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="strom-top",
        description="live per-class attribution/ledger view over the "
                    "STROM_DEBUG_PORT endpoint")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, required=True,
                    help="the serving process's STROM_DEBUG_PORT")
    ap.add_argument("--interval", type=float, default=2.0,
                    help="refresh interval in seconds")
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit (scripts, tests)")
    args = ap.parse_args(argv)

    def frame() -> str:
        attrib = fetch(args.host, args.port, "/attrib")
        ledger = fetch(args.host, args.port, "/ledger")
        health = fetch(args.host, args.port, "/health")
        return render_frame(attrib, ledger, health)

    try:
        if args.once:
            print(frame())
            return 0
        while True:
            out = frame()
            sys.stdout.write("\x1b[2J\x1b[H" + out + "\n")
            sys.stdout.flush()
            time.sleep(max(0.1, args.interval))
    except (urllib.error.URLError, OSError) as e:
        print(f"strom-top: cannot reach "
              f"http://{args.host}:{args.port}: {e}", file=sys.stderr)
        return 2
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
