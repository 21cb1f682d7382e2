"""ra_top — live terminal view of the Observatory (ISSUE 6).

Follows a JSONL snapshot ring (what ``tools/soak.py --obs`` or
``Observatory.to_jsonl`` writes) and renders the lane-health heat
summary, the top-K offender lanes, per-shard WAL fsync latency + queue
depth, the dispatch-pipeline counters, and the device plane (ISSUE 16:
compiles/recompiles, transfer ledger, memory watermarks).  stdlib-only,
works over ssh; the htop role of the reference's `ra:key_metrics`
console habit.

Usage:
    python tools/ra_top.py [path] [--interval S] [--once]

``path`` defaults to ``obs.jsonl`` in the cwd.  ``--once`` prints a
single frame without clearing the screen (what the tests drive; also
handy for cron/log capture).
"""
from __future__ import annotations

import json
import sys
import time

#: log2 histogram sparkline glyphs, low->high occupancy
_BARS = " .:-=+*#%@"


def _read_tail(path: str, n: int = 2) -> list:
    """Newest n parsable snapshots (oldest first); torn-tail tolerant."""
    try:
        with open(path) as f:
            lines = f.readlines()
    except OSError:
        return []
    out = []
    for raw in lines[-(n + 1):]:
        try:
            out.append(json.loads(raw))
        except ValueError:
            continue
    return out[-n:]


def _spark(hist: list) -> str:
    top = max(hist) if hist else 0
    if top <= 0:
        return _BARS[0] * len(hist)
    return "".join(
        _BARS[min(len(_BARS) - 1, int(v / top * (len(_BARS) - 1) + 0.999))]
        for v in hist)


def _fmt_rate(v: float) -> str:
    for div, suf in ((1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(v) >= div:
            return f"{v / div:.2f}{suf}"
    return f"{v:.1f}"


def render(snap: dict, prev: dict | None = None) -> str:
    """One frame of the dashboard as plain text."""
    lines: list = []
    eng = snap.get("engine", {})
    tel = eng.get("telemetry") or {}
    pipe = eng.get("pipeline", {})
    sampler = eng.get("sampler", {})
    ts = snap.get("ts", 0.0)
    lines.append(
        f"ra_top  seq={snap.get('seq', '?')}  "
        f"{time.strftime('%H:%M:%S', time.localtime(ts))}  "
        f"lanes={eng.get('lanes', '?')}x{eng.get('members', '?')}")
    # -- commit rate over the last window ---------------------------------
    if prev is not None:
        p_tel = prev.get("engine", {}).get("telemetry") or {}
        dt = max(ts - prev.get("ts", ts), 1e-9)
        # commit rate over the SAMPLER's own window: a JSONL export
        # faster than the harvest cadence re-embeds the same sample,
        # and the snapshot-ts delta would then read a running engine
        # as 0 cmds/s; "--" = no fresh sample between these snapshots
        dt_tel = (tel.get("ts", 0.0) - p_tel.get("ts", 0.0)
                  if tel.get("ts") and p_tel.get("ts") else 0.0)
        dc = (tel.get("committed_total", 0.0)
              - p_tel.get("committed_total", 0.0))
        cmds = _fmt_rate(dc / dt_tel) if dt_tel > 1e-9 else "--"
        di = (pipe.get("inner_steps", 0)
              - prev.get("engine", {}).get("pipeline", {})
              .get("inner_steps", 0))
        lines.append(f"rate    {cmds} cmds/s   "
                     f"{_fmt_rate(di / dt)} steps/s   window {dt:.1f}s")
    # -- lane health -------------------------------------------------------
    if tel:
        stalled = tel.get("stalled_lanes", 0)
        flag = " <<< STALLED LANES" if stalled else ""
        lines.append(
            f"lanes   stalled={stalled}{flag}  "
            f"commit_lag max={tel.get('commit_lag_max', 0)} "
            f"mean={tel.get('commit_lag_mean', 0)}  "
            f"apply_lag max={tel.get('apply_lag_max', 0)}  "
            f"leader_age_min={tel.get('leader_age_min', 0)}")
        hist = tel.get("commit_lag_hist")
        if hist:
            lines.append(f"lag     [{_spark(hist)}]  log2 buckets "
                         f"0..2^{len(hist) - 1}  n={sum(hist)}")
        top = tel.get("top_lanes") or []
        if top:
            rows = []
            for r, lane in enumerate(top[:8]):
                cl = (tel.get("top_commit_lag") or [0] * len(top))[r]
                st = (tel.get("top_stall_steps") or [0] * len(top))[r]
                if cl == 0 and st == 0:
                    continue
                rows.append(f"#{lane}(lag={cl},stall={st})")
            lines.append("top     " + (" ".join(rows) if rows
                                       else "(all lanes healthy)"))
    elif "telemetry" not in eng:
        lines.append("lanes   (no telemetry sampler attached)")
    if sampler:
        lines.append(
            f"sampler started={sampler.get('samples_started', 0)} "
            f"harvested={sampler.get('samples_harvested', 0)} "
            f"dropped={sampler.get('samples_dropped', 0)} "
            f"blocking_waits={sampler.get('blocking_waits', 0)}")
    # -- dispatch pipeline -------------------------------------------------
    if pipe:
        disp = pipe.get("dispatches", 0)
        inner = pipe.get("inner_steps", 0)
        fusion = f"{inner / disp:.1f}x" if disp else "-"
        lines.append(
            f"pipe    dispatches={disp} inner_steps={inner} "
            f"fusion={fusion} "
            f"in_flight={pipe.get('dispatches_in_flight', 0)} "
            f"window_syncs={pipe.get('window_syncs', 0)} "
            f"early_observes={pipe.get('early_observes', 0)}")
    # -- ingress plane (ISSUE 10) ------------------------------------------
    ing = snap.get("ingress") or {}
    if ing:
        if prev is not None:
            p_ing = prev.get("ingress") or {}
            dt = max(ts - prev.get("ts", ts), 1e-9)
            da = ing.get("accepted", 0) - p_ing.get("accepted", 0)
            rate = _fmt_rate(da / dt)
        else:
            rate = "--"
        shed = ing.get("shed_rows", 0)
        flag = " <<< SHEDDING" if shed and prev is not None and \
            shed > (prev.get("ingress") or {}).get("shed_rows", 0) else ""
        # the durability half of the backlog under durable/mesh runs
        # (ingress queue + unconfirmed WAL steps = uncommitted total)
        wp = ing.get("wal_pending_steps")
        wp_s = f" wal_pending={wp}" if wp is not None else ""
        lines.append(
            f"ingress {rate} acc/s  sessions={ing.get('sessions', 0)} "
            f"q={ing.get('queue_rows', 0)} "
            f"level={ing.get('ladder', {}).get('level_name', '?')} "
            f"dup={ing.get('dup_dropped', 0)} shed={shed}"
            f" rej={ing.get('rejected', 0)}{wp_s}{flag}")
    # -- wire plane (ISSUE 12) ---------------------------------------------
    wire = snap.get("wire") or {}
    if wire:
        p_wire = (prev.get("wire") or {}) if prev is not None else {}
        if prev is not None:
            dt = max(ts - prev.get("ts", ts), 1e-9)
            dr = wire.get("swept_rows", 0) - p_wire.get("swept_rows", 0)
            rate = _fmt_rate(dr / dt)
        else:
            rate = "--"
        # credit-level histogram over the window (falls back to the
        # lifetime totals on the first frame)
        levels = ("credit_ok", "credit_slow", "credit_defer",
                  "credit_reject", "credit_dup", "credit_shed")
        hist = [max(0, wire.get(k, 0) - p_wire.get(k, 0)) for k in levels] \
            if prev is not None else [wire.get(k, 0) for k in levels]
        names = ("ok", "slow", "defer", "rej", "dup", "shed")
        hist_s = " ".join(f"{n}={v}" for n, v in zip(names, hist) if v)
        errs = wire.get("protocol_errors", 0)
        lines.append(
            f"wire    {rate} rec/s  conns={wire.get('conns', 0)} "
            f"(sock={wire.get('socket_conns', 0)} "
            f"paused={wire.get('paused_conns', 0)})  "
            f"credit[{_spark(hist)}] {hist_s or 'idle'}"
            + (f"  errs={errs}" if errs else ""))
    # -- read plane (ISSUE 20) ---------------------------------------------
    rd = snap.get("read") or {}
    if rd:
        p_rd = (prev.get("read") or {}) if prev is not None else {}
        if prev is not None:
            dt = max(ts - prev.get("ts", ts), 1e-9)
            ds = rd.get("served", 0) - p_rd.get("served", 0)
            rate = _fmt_rate(ds / dt)
        else:
            rate = "--"
        # read_p99 from the phase attribution (the read_p99_ms SLO's
        # own signal); -1.0 is the repo-wide "never measured" sentinel
        p99 = ((eng.get("phases") or {}).get("read_e2e") or {}) \
            .get("p99_ms", -1.0)
        p99_s = f"{p99:.1f}ms" if p99 >= 0 else "--"
        stale = rd.get("stale_refused", 0)
        flag = " <<< REFUSING" if prev is not None and \
            stale > p_rd.get("stale_refused", 0) else ""
        shed = rd.get("shed", 0)
        lines.append(
            f"reads   {rate} srv/s  p99={p99_s}  "
            f"lease={rd.get('lease_coverage_pct', 0.0):.0f}%  "
            f"q={rd.get('queue_rows', 0)} shed={shed} "
            f"stale_refused={stale}{flag}")
    # -- device plane (ISSUE 16) -------------------------------------------
    dev = snap.get("device") or {}
    if dev:
        p_dev = (prev.get("device") or {}) if prev is not None else {}
        dre = dev.get("recompiles", 0)
        # <<< flag only on fresh recompiles (like the SHEDDING flag);
        # the drift attribution line sticks around once any recompile
        # happened — naming the drifting argument is the sentinel's job
        flag = " <<< RECOMPILING" \
            if prev is not None and dre > p_dev.get("recompiles", 0) else ""
        drift = ""
        if dre:
            for tag, ent in sorted((dev.get("per_fn") or {}).items()):
                if ent.get("last_drift"):
                    drift = f"\ndrift   {tag}: {ent['last_drift'][:68]}"
                    break
        lines.append(
            f"device  compiles={dev.get('compiles', 0)} re={dre} "
            f"{dev.get('compile_ms', 0.0):.0f}ms  "
            f"h2d={dev.get('h2d_events', 0)}/"
            f"{_fmt_rate(dev.get('h2d_bytes', 0))}B "
            f"d2h={dev.get('d2h_events', 0)}/"
            f"{_fmt_rate(dev.get('d2h_bytes', 0))}B  "
            f"live={dev.get('live_buffers', 0)}/"
            f"{_fmt_rate(dev.get('live_bytes', 0))}B "
            f"peak={_fmt_rate(dev.get('peak_live_bytes', 0))}B "
            f"freed={dev.get('buffers_freed', 0)}{flag}{drift}")
    # -- WAL shards --------------------------------------------------------
    wal = eng.get("wal") or {}
    shards = wal.get("shards") or []
    sys_wal = snap.get("system", {}).get("counters", {}).get("wal")
    if not shards and sys_wal:
        shards = [sys_wal]
    for sh in shards[:8]:
        sid = sh.get("shard", "-")
        lanes_sl = sh.get("lanes")
        lane_s = f" lanes={lanes_sl[0]}..{lanes_sl[1]}" \
            if isinstance(lanes_sl, list) and len(lanes_sl) == 2 else ""
        lines.append(
            f"wal[{sid}] fsync p50={sh.get('fsync_p50_ms', -1)}ms "
            f"p99={sh.get('fsync_p99_ms', -1)}ms "
            f"rec/fsync={sh.get('records_per_fsync', -1)} "
            f"queue={sh.get('queue_depth', 0)} "
            f"jobs={sh.get('jobs_pending', 0)} "
            f"lag={sh.get('confirm_lag_steps', 0)}{lane_s}")
    if len(shards) > 8:
        # a wide per-device mesh layout (one shard per lane device):
        # summarize the tail rather than silently truncating it
        rest = shards[8:]
        worst = max((s.get("fsync_p99_ms", -1) for s in rest),
                    default=-1)
        lag = max((s.get("confirm_lag_steps", 0) for s in rest),
                  default=0)
        jobs = sum(s.get("jobs_pending", 0) for s in rest)
        lines.append(f"wal[+{len(rest)}] worst fsync p99={worst}ms "
                     f"jobs={jobs} lag_max={lag}")
    df = (wal.get("disk_faults")
          or snap.get("system", {}).get("counters", {}).get("disk_faults"))
    if df and any(df.values()):
        hot = " ".join(f"{k}={v}" for k, v in sorted(df.items()) if v)
        lines.append(f"faults  {hot}")
    # -- SLO verdicts (ISSUE 9) --------------------------------------------
    slo = (snap.get("slo") or {}).get("objectives") or {}
    if slo:
        cells = []
        for name in sorted(slo):
            o = slo[name]
            verdict = o.get("verdict", "?")
            mark = {"ok": "OK", "no_data": "--",
                    "breach": "BREACH", "alert": "ALERT!"}.get(
                        verdict, verdict)
            val = o.get("value")
            val_s = "--" if val is None else f"{val:g}"
            cells.append(f"{name} {mark} {val_s}{o.get('op', '')}"
                         f"{o.get('threshold', '')} "
                         f"burn={o.get('burn_fast', 0):g}/"
                         f"{o.get('burn_slow', 0):g}")
        lines.append("slo     " + " | ".join(cells))
    # -- autotuner footer (ISSUE 9): the last decision + freeze state ------
    tun = snap.get("autotune") or {}
    if tun:
        knobs = tun.get("knobs") or {}
        knob_s = " ".join(f"{k}={v:g}" if isinstance(v, float)
                          else f"{k}={v}" for k, v in sorted(knobs.items()))
        last = tun.get("last_decision")
        if last:
            age = max(0.0, ts - last.get("ts", ts))
            dec = (f"{last.get('knob', '?')} {last.get('old', '?')}->"
                   f"{last.get('new', '?')} via {last.get('phase', '?')}"
                   f"/{last.get('objective', '?')} {age:.0f}s ago")
        else:
            dec = "no decisions"
        frozen = f" FROZEN({tun.get('freeze_reason')})" \
            if tun.get("frozen") else ""
        lines.append(f"tuner   {dec}{frozen}")
        lines.append(f"knobs   {knob_s}  decisions="
                     f"{tun.get('decisions', 0)} "
                     f"cooldown={tun.get('cooldown_left', 0)}")
    # -- counters self-metric ---------------------------------------------
    dropped = snap.get("counters", {}).get("self", {}) \
        .get("telemetry_dropped")
    if dropped:
        lines.append(f"WARN    telemetry_dropped={dropped} "
                     "(instrumentation/registry mismatch)")
    # -- last incident (flight recorder, ISSUE 7): a stalled soak must
    # be explainable from the live view — what escalated, where, when,
    # and which bundle to feed tools/ra_trace.py
    inc = (snap.get("blackbox") or {}).get("last_incident")
    if inc:
        age = max(0.0, ts - inc.get("ts", ts))
        bundle = inc.get("path") or ""
        bundle = bundle.rsplit("/", 1)[-1]
        lines.append(
            f"incident {inc.get('reason', '?')} @ "
            f"{inc.get('where', '?')}  {age:.0f}s ago  "
            f"{(inc.get('what') or '')[:36]}  bundle={bundle}")
    return "\n".join(lines)


def main(argv: list) -> int:
    once = "--once" in argv
    interval = 1.0
    args: list = []
    it = iter(argv)
    for a in it:
        if a == "--interval":
            # consume the interval's VALUE operand too, or it would be
            # mistaken for the snapshot path ("ra_top --interval 2")
            interval = float(next(it, "1.0"))
        elif not a.startswith("--"):
            args.append(a)
    path = args[0] if args else "obs.jsonl"
    if once:
        tail = _read_tail(path, 2)
        if not tail:
            print(f"ra_top: no snapshots at {path}")
            return 1
        print(render(tail[-1], tail[-2] if len(tail) > 1 else None))
        return 0
    try:
        while True:
            tail = _read_tail(path, 2)
            frame = render(tail[-1], tail[-2] if len(tail) > 1 else None) \
                if tail else f"ra_top: waiting for snapshots at {path} ..."
            # ANSI home+clear-below: repaint without scrollback spam
            sys.stdout.write("\x1b[H\x1b[J" + frame + "\n")
            sys.stdout.flush()
            time.sleep(interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
