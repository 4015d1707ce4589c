"""From a runner's record to the one line the driver reads.

A runner returns a record (a dict): what it counted and timed, the trace it
took, and the checks that failed. This module turns it into
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}``:
end-to-end metrics straight from the record, per-layer metrics through
each metric's own reader, found by name. A reader that returns ``None``
found nothing to read and its metric is left out of the line.
"""

from __future__ import annotations

from typing import Any, Dict

from benchmark.harness import cells, device as device_mod, flops, trace as trace_mod


def traced_window(record: Dict[str, Any]):
    """(trace, lo, hi) of a traced run, or ``None`` where there is none."""
    tr, win = record.get("trace"), record.get("trace_window")
    if tr is None or win is None or not tr.devices:
        return None
    return tr, win[0], win[1]


def result_line(cell: cells.Cell, record: Dict[str, Any], traced: bool) -> Dict[str, Any]:
    devices = record["devices"]
    dev = device_mod.device_summary(devices)
    dev["memory_peak_bytes"] = device_mod.memory_peak_bytes(devices)
    failures = list(record["failures"])
    metrics: Dict[str, Dict[str, Any]] = {}
    line: Dict[str, Any] = {}
    if not traced:
        for m in cell.end_to_end:
            value = record["end_to_end"].get(m.name)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
    else:
        tw = traced_window(record)
        if tw is not None:
            tr, lo, hi = tw
            busy = [trace_mod.busy_seconds(d, lo, hi) for d in tr.devices]
            dev["busy_s"] = sum(busy) / len(busy)
            dev["window_s"] = (hi - lo) * 1e-9
            if not dev["busy_s"] > 0:
                failures.append("no operation ran on the device in the traced window")
            fullest = max(tr.devices, key=lambda d: trace_mod.busy_seconds(d, lo, hi))
            line["breakdown"] = {
                "device_ops": trace_mod.top_ops(tr, lo, hi),
                "idle_gaps": trace_mod.idle_gaps_by_span(
                    fullest, [s for s in tr.spans if (s.start, s.end) != (lo, hi)], lo, hi,
                ),
            }
        elif not record.get("rehearsal"):      # the CPU has no device plane
            failures.append("the traced run produced no device trace")
        for m in cell.per_layer:
            try:
                value = cells.load_reader(m).read(record)
            except flops.UnknownDevice:
                if not record.get("rehearsal"):    # the CPU has no peaks either
                    raise
                value = None
            except flops.UnsupportedShape as e:
                # counts or peaks that do not cover this configuration: the
                # metric is left out, never made from another core's numbers
                record.setdefault("notes", {})[m.name] = str(e)
                value = None
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
    record["failures"] = failures
    line = {
        "correct": not failures,
        "attempted": int(record["attempted"]),
        "failed": int(record["failed"]),
        "metrics": metrics,
        "device": dev,
        **line,
    }
    return line


def detail(record: Dict[str, Any]) -> Dict[str, Any]:
    """What a person reading the run's log wants beside the result line."""
    keep = ("cell", "seed", "lanes", "opp_lanes", "window", "setup", "failures", "agreement", "notes")
    out = {k: record[k] for k in keep if k in record}
    out["memory_stats"] = device_mod.memory_stats(record["devices"])
    return out
