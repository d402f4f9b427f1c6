#!/usr/bin/env python3
"""A traced run's device seconds by ``jax.named_scope`` (PERF.md section 5).

    XLA_FLAGS="--xla_dump_to=<dump> --xla_dump_hlo_as_text" \\
        python3 benchmark/run.py --workload <cell> ... --trace 1 --out <out>
    python scripts/trace_scopes.py <out>/trace <dump> [--out table.json]

A TPU trace names a device operation by its HLO instruction and nothing
else: which layer half a ``fusion.238`` belongs to is in the compiled
module's ``metadata={op_name=...}``, which the run's own compile dumps (a
COLD start: a program read from the compile cache is not dumped). Each
operation of the first device, inside the benchmark's traced slice, is put
to the execution of the module that spans it, that module is matched to a
dumped text by the instruction names the two share, and its self time (a
``while`` without its body) goes to the outermost ``dgi_*`` scope of its
``op_name`` -- ``dgi_attention`` / ``dgi_experts`` / ``dgi_mlp`` /
``dgi_head`` (``models/llama.py``) -- or to ``(none)``. Without a dump the
table still lists each top operation with its result and operand shapes,
as the trace names it."""
import argparse
import glob
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "benchmark"))
from harness import trace_reduce  # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("trace")
ap.add_argument("dump", nargs="?")
ap.add_argument("--out")
ap.add_argument("--top", type=int, default=30)
args = ap.parse_args()

from jax.profiler import ProfileData  # noqa: E402

path = trace_reduce.find_xplane(args.trace) \
    if os.path.isdir(args.trace) else args.trace
data = ProfileData.from_file(path)
device = next(p for p in data.planes
              if p.name.startswith("/device:") and "TPU" in p.name)
lines = {ln.name: ln for ln in device.lines}


def events(line):
    for e in line.events:
        a = float(e.start_ns) * 1e-9
        yield e.name, a, a + float(e.duration_ns) * 1e-9


# the benchmark's traced slice: its ``bench.slice`` annotation on a host line
sliced = next(((a, b) for p in data.planes if p.name.startswith("/host:")
               for ln in p.lines for name, a, b in events(ln)
               if name == "bench.slice"), None)
ops = list(events(lines["XLA Ops"]))
lo, hi = sliced or (min(a for _, a, _ in ops),
                                    max(b for _, _, b in ops))
runs = sorted((a, b, name) for name, a, b in events(lines["XLA Modules"])
              if b > lo and a < hi)

# module texts the run's compiles dumped: {module name: [{instr: op_name}]}
INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$", re.M)
OP_NAME = re.compile(r'op_name="([^"]*)"')


def instructions(text):
    out = {}
    for m in INSTR.finditer(text):
        named = OP_NAME.search(m.group(2))
        out[m.group(1)] = named.group(1) if named else ""
    return out


dumped = {}
for f in sorted(glob.glob(os.path.join(args.dump or "/nonexistent", "**",
                                       "*after_optimizations.txt"),
                          recursive=True)):
    text = open(f).read()
    head = re.match(r"HloModule ([\w.\-]+)", text)
    if head:
        dumped.setdefault(head.group(1), []).append(instructions(text))

# self seconds by (module execution's name, full instruction)
by_run, hlo = {}, {}
at = 0
for full, a, b in sorted(ops, key=lambda e: e[1]):
    if b <= lo or a >= hi:
        continue
    while at < len(runs) and runs[at][1] <= a:
        at += 1
    name = runs[at][2] if at < len(runs) and runs[at][0] <= a else "(none)"
    short = trace_reduce.short_name(full)
    by_run.setdefault(name, []).append((short, max(a, lo), min(b, hi)))
    hlo.setdefault((name, short), full)

SCOPE = re.compile(r"dgi_\w+")
rows, scopes = [], {}
for run, evs in by_run.items():
    module = re.sub(r"\(\d+\)$", "", run)
    seen = {short for short, _, _ in evs}
    texts = dumped.get(module, [])
    text = max(texts, key=lambda t: len(seen & t.keys()), default={})
    for short, sec in trace_reduce.self_times(evs).items():
        full = hlo[run, short]
        found = SCOPE.findall(text.get(short, ""))
        scope = found[0] if found else "(none)"
        key = (module, scope)
        scopes[key] = scopes.get(key, 0.0) + sec
        rows.append({"module": module, "op": short, "s": sec, "scope": scope,
                     "inner": found[-1] if found else "",
                     "op_name": text.get(short, "")[-120:],
                     "hlo": full[:400]})
rows.sort(key=lambda r: -r["s"])
busy = sum(r["s"] for r in rows)
table = {
    "trace": path, "window_s": hi - lo, "busy_s": busy,
    "modules_dumped": {k: len(v) for k, v in dumped.items()},
    "by_scope": sorted(([m, s, sec, sec / busy] for (m, s), sec
                        in scopes.items()), key=lambda r: -r[2]),
    "top": rows[:args.top],
}
for m, s, sec, share in table["by_scope"][:24]:
    print(f"{sec:8.3f} s {100 * share:5.1f} %  {m}  {s}")
for r in table["top"]:
    print(f"{r['s']:8.3f} s  {r['module']}  {r['op']}  [{r['scope']}"
          f"{'/' + r['inner'] if r['inner'] != r['scope'] else ''}]  "
          f"{r['hlo'][len(r['op']) + 4:160]}")
if args.out:
    with open(args.out, "w") as f:
        json.dump(table, f, indent=1)
