"""A traced step by the program's layer scopes, at self time; or, without a
trace, a compiled step's instructions by layer (docs/observability.md,
"Reading a step by layer"; PERF.md section 5's tables are this tool's output).

    python tools/step_ledger.py benchmark/_trace/<cell>/plugins/profile/*/*.xplane.pb \\
        --steps 10 [--rows 40] [--by layer,pass,op] [--json]
    python tools/step_ledger.py --hlo step.txt [--json]

The first form reads a saved ``.xplane.pb`` (``.gz`` too) with the
benchmark's own reader (``benchmark/trace_reduce.py:read_xplane``) and prints
``benchmark/scope_ledger.py:table``: milliseconds a step of **self time** (an
op's interval minus the ops inside it, so a ``while`` and its body count
once) by layer (the innermost ``hvd_*`` scope of the op) -> pass (``forward``:
under ``jvp(`` and no ``transpose(``; ``backward``; ``neither``) ->
instruction group (``fusion.45`` -> ``fusion``), **every row**, each with
XLA's own ``flops`` and ``bytes_accessed`` where the file has them, the ops
under no ``hvd_*`` scope last with the scope they do have, and a closing line
``sum of rows = busy_s / steps``.  The per-layer metrics ``attn_proj_ms``,
``mlp_ms``, ``block_rest_ms``, ``lm_head_ms``, ``optimizer_update_ms`` and
``step_unattributed_pct`` are sums of these rows.  ``--steps`` is the steps
the window holds (``traffic/<mix>.json``'s ``trace_steps``).

``--hlo`` reads an optimized-HLO text (``step.as_text()`` of a compiled
step): instructions and result bytes by layer and opcode (fused
computations' insides left out), then every ``copy``, ``copy-done``,
``slice-done`` and ``transpose`` with its shape and ``op_name``.

No chip is needed for either; ``--json`` prints what a table in PERF.md was
pasted from.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import scope_ledger, trace_reduce, xplane_raw  # noqa: E402

# ``%name = <result type> opcode(`` of an HLO text; the type may be a tuple.
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\(?\w+\[[\d,]*\].*?) ([\w\-]+)\(")
ARRAY = re.compile(r"\b(pred|[su]\d+|bf16|f\d+|c\d+)\[([\d,]*)\]")
RELAYOUTS = ("copy", "copy-done", "slice-done", "transpose")


# ---------------------------------------------------------------------------
# A trace
# ---------------------------------------------------------------------------


def op_stats(path: str) -> dict:
    """``{op's instruction text: {"flops", "bytes_accessed", ...}}`` of the
    file's device planes (one program on every device: merged)."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        raw = f.read()
    merged = {}
    for events in xplane_raw.event_stats(
            raw, trace_reduce.DEVICE_PLANE.pattern).values():
        merged.update(events)
    return merged


def ledger(path: str, steps: int, by: tuple) -> dict:
    """The table of one saved trace and what it must add up to."""
    trace = trace_reduce.read_xplane(path, steps=steps)
    rows = scope_ledger.table(trace, by, op_stats(path))
    return {"xplane": path, "steps": steps, "devices": sorted(trace.devices),
            "by": list(by), "rows": rows,
            "sum_of_rows_ms": sum(row["ms"] for row in rows),
            "busy_ms_per_step": scope_ledger.busy_ms(trace),
            "step_unattributed_pct": scope_ledger.unattributed_pct(trace, {})}


def print_ledger(found: dict, rows: int, out) -> None:
    by = found["by"]
    totals = {}
    for row in found["rows"]:
        totals[row[by[0]]] = totals.get(row[by[0]], 0.0) + row["ms"]
    print(f"# {found['xplane']}: {found['steps']} steps, devices "
          f"{found['devices']}; ms a step of self time", file=out)
    print(f"{'ms':>10} {'calls':>8} {'GFLOP':>9} {'MB':>9}  "
          + " / ".join(by), file=out)
    group, shown = None, 0
    for row in found["rows"]:
        if row[by[0]] != group:
            group = row[by[0]]
            print(f"{totals[group]:10.3f} {'':>8} {'':>9} {'':>9}  "
                  f"{group}", file=out)
        if shown == rows:
            continue   # the groups' totals still print
        shown += 1
        rest = " / ".join(str(row[k]) for k in by[1:]) or "(all)"
        if row.get("scope"):
            rest += f"  [{row['scope']}]"
        print(f"{row['ms']:10.3f} {row['calls']:8.1f} "
              f"{row.get('flops', 0) / 1e9:9.2f} "
              f"{row.get('bytes', 0) / 1e6:9.1f}    {rest}", file=out)
    if shown < len(found["rows"]):
        print(f"  ... {len(found['rows']) - shown} more rows (--rows)",
              file=out)
    print(f"step_unattributed_pct = {found['step_unattributed_pct']:.3f}",
          file=out)
    print(f"sum of rows = {found['sum_of_rows_ms']:.3f} ms; "
          f"busy_s / steps = {found['busy_ms_per_step']:.3f} ms", file=out)


# ---------------------------------------------------------------------------
# A compiled step's text
# ---------------------------------------------------------------------------


def result_bytes(result_type: str) -> float:
    """Bytes of an instruction's result (a tuple's arrays summed)."""
    total = 0.0
    for dtype, dims in ARRAY.findall(result_type):
        bits = 8 if dtype == "pred" else int(re.sub(r"\D", "", dtype))
        elements = 1
        for n in filter(None, dims.split(",")):
            elements *= int(n)
        total += elements * bits / 8
    return total


def census(text: str) -> dict:
    """Instructions and result bytes of an optimized-HLO text by layer and
    opcode, the insides of fused computations left out (a fusion is one
    instruction), and the relayouts one by one."""
    fused = set(re.findall(r"\bfusion\([^\n]*?calls=%([\w.\-]+)", text))
    rows, relayouts, skipping = {}, [], False
    for line in text.splitlines():
        header = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$", line)
        if header:
            skipping = header.group(1) in fused
            continue
        found = INSTRUCTION.match(line)
        if skipping or not found:
            continue
        _, result, opcode = found.groups()
        name = re.search(r'op_name="([^"]*)"', line)
        scope = name.group(1) if name else ""
        layer = scope_ledger.layer_of_scope(scope) \
            or scope_ledger.UNATTRIBUTED
        row = rows.setdefault((layer, opcode), {"instructions": 0,
                                                "result_bytes": 0.0})
        row["instructions"] += 1
        row["result_bytes"] += result_bytes(result)
        if opcode in RELAYOUTS:
            relayouts.append({"opcode": opcode, "layer": layer,
                              "result": result.split("{")[0],
                              "result_bytes": result_bytes(result),
                              "op_name": scope})
    ranked = sorted(rows.items(), key=lambda kv: (
        kv[0][0] == scope_ledger.UNATTRIBUTED, kv[0][0],
        -kv[1]["result_bytes"]))
    return {"rows": [{"layer": layer, "opcode": opcode, **row}
                     for (layer, opcode), row in ranked],
            "relayouts": sorted(relayouts,
                                key=lambda r: -r["result_bytes"])}


def print_census(found: dict, out) -> None:
    print(f"{'instructions':>12} {'result MB':>11}  layer / opcode", file=out)
    for row in found["rows"]:
        print(f"{row['instructions']:12d} {row['result_bytes'] / 1e6:11.2f}"
              f"  {row['layer']} / {row['opcode']}", file=out)
    print(f"# {len(found['relayouts'])} of {', '.join(RELAYOUTS)}", file=out)
    for r in found["relayouts"]:
        print(f"{r['result_bytes'] / 1e6:11.2f} MB  {r['opcode']} "
              f"{r['result']}  {r['layer']}  {r['op_name']}", file=out)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None, out=sys.stdout) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", nargs="?", help="a saved .xplane.pb (or .gz)")
    ap.add_argument("--steps", type=int, default=10,
                    help="steps the traced window holds")
    ap.add_argument("--rows", type=int, default=-1,
                    help="rows printed (default: every row)")
    ap.add_argument("--by", default="layer,pass,op",
                    help="of layer, pass, op")
    ap.add_argument("--hlo", help="an optimized-HLO text: the census")
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args(argv)
    if (args.xplane is None) == (args.hlo is None):
        ap.error("one of a saved .xplane.pb and --hlo <file>")
    if args.hlo:
        with open(args.hlo) as f:
            found = census(f.read())
    else:
        found = ledger(args.xplane, args.steps, tuple(args.by.split(",")))
    if args.json:
        print(json.dumps(found), file=out)
    elif args.hlo:
        print_census(found, out)
    else:
        print_ledger(found, args.rows, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
