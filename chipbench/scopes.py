"""Device time by the program's own spans, from a profiler trace.

The program names the layers of a round with ``jax.named_scope``
(``round.sample``, ``round.state_gather``, ``round.local_phase``,
``round.encode``, ``round.aggregate``, ``round.downlink``,
``round.state_update``), its wire kernels by their ``pallas_call`` name,
and the host steps of its engine with ``TraceAnnotation``s
(``engine.plan_cohorts``, ``engine.dispatch``, ``engine.fetch_metrics``).
The names are written here again, not imported: the yardstick stays when
the program changes, and a program without them reads as one without
them.

``events(path)`` reads an ``.xplane.pb`` as ``trace.events`` does, and
keeps besides each chip's ``XLA Modules`` and the host's
``PJRT_LoadedExecutable_Execute`` events and the engine's host spans.  A
TPU trace's operation events carry no name stack, so ``assign`` gives
each operation the ``round.*`` span its instruction's name stack names in
the compiled program's text (``hlo_stacks``).  ``span_times`` gives each
span the union of its *leaf* operations' intervals over a window: an
operation that holds others on the device's timeline (the rounds'
``while``, a ``call``) is a wrapper and counts only through what it
holds, so nothing is counted twice.  ``clock_offset`` puts the device's
timestamps on the host's clock, and ``gaps`` names each idle gap by the
innermost host span, the engine's among them, that held it.  All but
``events`` are pure, so a small recorded trace tests them.
"""

from __future__ import annotations

import re
import sys

from chipbench import trace

ROUND_SPANS = ("round.sample", "round.state_gather", "round.local_phase",
               "round.encode", "round.aggregate", "round.downlink",
               "round.state_update")
ENGINE_SPANS = ("engine.plan_cohorts", "engine.dispatch",
                "engine.fetch_metrics")
#: host spans that name an idle gap: the harness's and the engine's
GAP_SPANS = tuple(trace.HOST_SPANS) + ENGINE_SPANS
EXECUTE = "PJRT_LoadedExecutable_Execute"
MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
_ROUND = re.compile(r"round\.[a-z_]+")
_STACK = re.compile(
    r'%([\w.-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"')


def profile_options():
    """The profiler's options for a scoped trace: host spans and launches
    (``TraceMe``s), no Python function calls and no HLO protos, which
    nothing here reads and which make the trace large and slow to write
    and read."""
    import jax
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    return options


def scope(name_stack: str | None) -> str | None:
    """The innermost ``round.*`` span named in an operation's name
    stack, or ``None``."""
    found = _ROUND.findall(name_stack or "")
    return found[-1] if found else None


def events(path: str) -> dict:
    """{"devices": {plane: [(op, start_ns, end_ns)]}, "modules":
    {plane: [(module, start_ns, end_ns)]}, "host": [(name, start_ns,
    end_ns)]} from one ``.xplane.pb``; the host list holds the harness's
    and the engine's spans and the program launches (``EXECUTE``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    wanted = set(GAP_SPANS) | {EXECUTE}
    for plane in data.planes:
        if trace.CHIP_PLANE.match(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops.extend((trace.op_name(e.name), e.start_ns, e.end_ns)
                               for e in line.events)
                elif line.name == MODULES_LINE:
                    mods.extend((e.name, e.start_ns, e.end_ns)
                                for e in line.events)
            devices[plane.name] = ops
            modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.end_ns)
                            for e in line.events if e.name in wanted)
    return {"devices": devices, "modules": modules, "host": host}


def hlo_stacks(hlo_text: str) -> dict:
    """{instruction: name stack} from a compiled module's text (each
    instruction's ``metadata={... op_name="..."}``)."""
    return dict(_STACK.findall(hlo_text))


def assign(ev: dict, module: str, stacks: dict) -> dict:
    """``ev`` with each device operation ``(op, start, end)`` given its
    span: for one that ran inside a module named ``module`` (``jit_run``
    for ``jit_run(1234)``), the ``round.*`` span its name stack in
    ``stacks`` (``hlo_stacks``) names, else ``None``.  An instruction
    the text gives no name stack was made by a compiler pass (a sort or
    a loop that lowers a scatter, a copy); it takes the span of the
    spanned operations that ran just before and just after it, where the
    two agree, and no span where they differ."""
    devices = {}
    for plane, ops in ev["devices"].items():
        inside = [(s, e) for n, s, e in ev["modules"].get(plane, ())
                  if n.split("(", 1)[0] == module]
        rows, made = [], set()
        for op, s, e in ops:
            sp = None
            if any(ms <= s and e <= me for ms, me in inside):
                if op in stacks:
                    sp = scope(stacks[op])
                else:
                    made.add(len(rows))
            rows.append([op, s, e, sp])
        order = sorted(range(len(rows)), key=lambda i: rows[i][1])
        before, last = {}, None
        for i in order:
            if i in made:
                before[i] = last
            elif rows[i][3]:
                last = rows[i][3]
        after = None
        for i in reversed(order):
            if i in made:
                rows[i][3] = after if before[i] == after else None
            elif rows[i][3]:
                after = rows[i][3]
        devices[plane] = [tuple(r) for r in rows]
    return {**ev, "devices": devices}


def leaves(ops) -> list:
    """The operations that hold no other operation on the timeline."""
    order = sorted((op for op in ops if op[2] > op[1]),
                   key=lambda op: (op[1], -op[2]))
    wrapper = [False] * len(order)
    open_: list = []                     # indices of enclosing operations
    for i, op in enumerate(order):
        while open_ and order[open_[-1]][2] <= op[1]:
            open_.pop()
        if open_ and op[2] <= order[open_[-1]][2]:
            wrapper[open_[-1]] = True
        open_.append(i)
    return [op for op, w in zip(order, wrapper) if not w]


def span_times(ev: dict, lo: float, hi: float) -> dict:
    """Device time (ns) in the window [lo, hi], averaged over the chips:
    per ``round.*`` span the union of its leaf operations' intervals;
    ``unscoped`` the busy time (``trace.busy_ns``, every operation) that
    no scoped leaf covers; ``busy``; and ``leaves``, each leaf operation's
    summed time keyed ``<span>/<op>``."""
    devs = ev["devices"]
    if not devs:
        raise RuntimeError("the trace holds no device operations")
    spans: dict = {}
    per_leaf: dict = {}
    unscoped = busy = 0.0
    for ops in devs.values():
        leaf = leaves(ops)
        for name in {op[3] for op in leaf if op[3]}:
            spans[name] = spans.get(name, 0.0) + trace.busy_ns(
                [op[:3] for op in leaf if op[3] == name], lo, hi)
        scoped = trace.busy_ns([op[:3] for op in leaf if op[3]], lo, hi)
        dev_busy = trace.busy_ns([op[:3] for op in ops], lo, hi)
        busy += dev_busy
        unscoped += dev_busy - scoped
        for name, s, e, sp in leaf:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                k = f"{sp or UNSCOPED}/{name}"
                per_leaf[k] = per_leaf.get(k, 0.0) + (e - s)
    n = len(devs)
    return {"spans": {k: v / n for k, v in spans.items()},
            "unscoped": unscoped / n, "busy": busy / n,
            "leaves": {k: v / n for k, v in per_leaf.items()}}


def kernel(op: str) -> str:
    """``select_slots.12`` -> ``select_slots``: an instruction's name
    without XLA's number, which for a Pallas kernel is its given name."""
    return op.rsplit(".", 1)[0] if op.rsplit(".", 1)[-1].isdigit() else op


def clock_offset(ev: dict) -> float:
    """Nanoseconds to add to a device timestamp to put it on the host's
    clock.  A program cannot start on the chip before the host call that
    launched it, so the offset is the largest lead of a launch's start
    over its module's start.  The k-th module on a chip from the last is
    the k-th launch from the last: the trace ends after the last module,
    and may have missed the launches of its first ones.  0 where the
    trace holds no launch or no module."""
    launches = sorted(s for n, s, _ in ev["host"] if n == EXECUTE)
    leads = [h - d for mods in ev["modules"].values()
             for h, d in zip(reversed(launches),
                             sorted((s for _, s, _ in mods), reverse=True))]
    return float(max(leads, default=0.0))


def gaps(ev: dict, lo: float, hi: float, offset: float = 0.0) -> list:
    """[(host span, ns)] for each idle gap of each chip in [lo, hi], named
    by the innermost of ``GAP_SPANS`` (not ``window``) that holds the
    gap's middle, read on the host's clock (``offset`` added)."""
    host = [(n, s, e) for n, s, e in ev["host"] if n in GAP_SPANS]
    out = []
    for ops in ev["devices"].values():
        merged = trace.union(trace.clip([op[1:3] for op in ops], lo, hi))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                out.append((trace.label(host, (s + e) / 2 + offset), e - s))
    return sorted(out, key=lambda g: -g[1])


def span_ms(rec: dict, metric: str, names) -> float | None:
    """Device time per round under the spans ``names``, in ms, from the
    ``round_spans`` probe; ``None``, and why on standard error, where the
    probe found no scoped operation."""
    probe = _probe(rec, metric)
    if probe is None:
        return None
    ns = sum(probe["spans_ns"].get(n, 0.0) for n in names)
    return ns / probe["rounds"] / 1e6


def unscoped_share(rec: dict, metric: str) -> float | None:
    """Busy time under no ``round.*`` span over busy time, in %."""
    probe = _probe(rec, metric)
    if probe is None or probe["busy_ns"] <= 0:
        return None
    return 100.0 * probe["unscoped_ns"] / probe["busy_ns"]


def _probe(rec: dict, metric: str):
    probe = rec["probes"].get("round_spans")
    if not probe or not probe["scoped"]:
        print(f"chipbench: {metric}: not reported: no device operation of "
              "the traced chunks carries a round.* span", file=sys.stderr)
        return None
    return probe
