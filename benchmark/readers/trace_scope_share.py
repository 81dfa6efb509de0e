"""Device time of one jitted program by the scopes and passes its code
names, as a share of the time in which any operation ran, %, averaged
over the devices.

The trace names an operation by its HLO instruction; the program's own
registry (``ray_tpu.observability.device_programs``) gives, for the
executable that ran, each instruction's ``op_name`` path, such as
``jit(train_step)/transpose(jvp(layers))/while/body/closed_call/
checkpoint/rematted_computation/mlp/dot_general``. From a path:

- its scopes, outermost first: the ``jax.named_scope``s of the program,
  with the transforms around a name taken off and the segments JAX and
  the primitive add left out (``layers/mlp`` above);
- its pass: ``remat`` where ``rematted_computation`` is in it, else
  ``bwd`` under a ``transpose(``, else ``fwd`` under a ``jvp(``, else
  ``other`` (the update, and what the compiler hoisted out of a pass).

A metric file names the ``program`` and may give ``scope``, a regex that
has to match one of the scopes whole, and ``pass``, one that has to
match the pass whole. An operation's time is its own: an event less the
events nested in it (``benchmark.trace.self_times``). Only paths under
``jit(<program>)`` count. Nothing where the program has no registry or
noted no executable of that name. ``commentary`` in the file prints the
whole anatomy on commentary lines.
"""

import bisect
import re
import time
from collections import defaultdict

from benchmark.readers._registry import device_programs
from benchmark.trace import COLLECTIVE, innermost, self_times, union

TRANSFORMS = re.compile(r"^(?:(?:jvp|transpose|vmap)\()*([^()]*)\)*$")
ADDED_BY_JAX = re.compile(  # control flow, remat, jit(f), einsum specs
    r"^(|while|body|cond|closed_call|checkpoint|rematted_computation|"
    r"branch_\d+_fun|shard_map|pjit|custom_[jv][jv]p_call\w*|\w+\(.*\)|"
    r".*->.*)$")
PASSES = ("fwd", "remat", "bwd", "other")


def scopes_of(path: str):
    """The named scopes of an op_name path, outermost first."""
    out = []
    for segment in path.split("/")[1:-1]:  # jit(<program>) ... primitive
        bare = TRANSFORMS.match(segment)
        name = bare.group(1) if bare else segment
        if not ADDED_BY_JAX.match(name):
            out.append(name)
    return out


def pass_of(path: str) -> str:
    if "rematted_computation" in path:
        return "remat"
    if "transpose(" in path:
        return "bwd"
    return "fwd" if "jvp(" in path else "other"


def own_by_op(run):
    """{instruction name: own seconds a device} of the trace's ``XLA
    Ops`` events; reduced once a run and kept on it."""
    if "_own_by_op" not in run:
        t0 = time.perf_counter()
        devices = run["trace"].devices
        own = defaultdict(float)
        for events in devices.values():
            for op, ns in self_times(events):
                own[op] += ns / 1e9 / len(devices)
        print(f"[reader] trace_scope_share: own times of "
              f"{sum(map(len, devices.values()))} events in "
              f"{time.perf_counter() - t0:.3f} s", flush=True)
        run["_own_by_op"] = own
    return run["_own_by_op"]


def scope_table_of(run, program: str):
    """The program's ``{instruction: op_name path}``, asked for once a
    run; None where there is no registry or no such program in it."""
    kept = run.setdefault("_scope_tables", {})
    if program not in kept:
        registry, t0 = device_programs(), time.perf_counter()
        kept[program] = registry and registry.scope_table_of(program)
        if kept[program] is not None:
            print(f"[reader] trace_scope_share: scope table of {program}, "
                  f"{len(kept[program])} instructions, in "
                  f"{time.perf_counter() - t0:.3f} s", flush=True)
    return kept[program]


def read(metric, run):
    program = metric["program"]
    table = scope_table_of(run, program)
    busy = run["trace"].busy_s()
    if table is None or busy <= 0:
        return None
    under = f"jit({program})"
    own = defaultdict(float)  # by op_name path; "" where it is not the program's
    for op, s in own_by_op(run).items():
        path = table.get(op, "")
        own[path if path.startswith(under) else ""] += s
    scope, which = metric.get("scope"), metric.get("pass")
    seconds = sum(
        s for path, s in own.items()
        if path
        and (scope is None
             or any(re.fullmatch(scope, x) for x in scopes_of(path)))
        and (which is None or re.fullmatch(which, pass_of(path))))
    if metric.get("commentary"):
        comment(metric["name"], run, table, under, own, busy)
    return 100.0 * seconds / busy


def comment(name, run, table, under, own, busy):
    """The anatomy behind the shares: every chain of scopes with its
    share by pass, the instructions with no op_name, on several chips the
    exposed collective time by chain, and the programs that ran."""
    t0 = time.perf_counter()
    chains = defaultdict(lambda: dict.fromkeys(PASSES, 0.0))
    for path, s in own.items():
        if path:
            chains["/".join(scopes_of(path)) or "(no scope)"][
                pass_of(path)] += s
    by_pass = {p: sum(c[p] for c in chains.values()) for p in PASSES}
    print(f"[reader] {name}: busy {busy:.4f} s a device; % of busy by pass: "
          + ", ".join(f"{p} {100 * s / busy:.2f}" for p, s in by_pass.items())
          + f", no op_name under {under} {100 * own[''] / busy:.2f}",
          flush=True)
    for chain, c in sorted(chains.items(), key=lambda kv: -sum(kv[1].values())):
        total = sum(c.values())
        if total >= 0.0005 * busy:
            print(f"[reader] {name}:   {chain} {100 * total / busy:.2f} ("
                  + ", ".join(f"{p} {100 * s / busy:.2f}"
                              for p, s in c.items() if s) + ")", flush=True)
    bare = defaultdict(float)
    for op, s in own_by_op(run).items():
        if not table.get(op, "").startswith(under):
            bare[re.sub(r"[.\d]+$", "", op)] += s
    print(f"[reader] {name}: longest without an op_name, % of busy: "
          + ", ".join(f"{op} {100 * s / busy:.3f}" for op, s in sorted(
              bare.items(), key=lambda kv: -kv[1])[:8]), flush=True)
    exposed = exposed_collectives_by_chain(run["trace"], table)
    if exposed:
        print(f"[reader] {name}: exposed collective seconds a device by "
              "scope (overlapping collectives each counted): "
              + ", ".join(f"{chain} {s:.4f}" for chain, s in sorted(
                  exposed.items(), key=lambda kv: -kv[1])[:12]), flush=True)
    programs = defaultdict(int)
    for events in run["trace"].modules.values():
        for _s, _e, module in events:
            programs[module.split("(")[0]] += 1
    print(f"[reader] {name}: programs on the XLA Modules line: "
          + ", ".join(f"{m} x{n}" for m, n in sorted(programs.items())),
          flush=True)
    print(f"[reader] {name}: these lines took "
          f"{time.perf_counter() - t0:.3f} s", flush=True)


def exposed_collectives_by_chain(trace, table):
    """{chain of scopes and pass: seconds a device} of each collective
    operation's time that no other operation on its device covers."""
    out = defaultdict(float)
    w0, w1 = trace.window
    for n, events in trace.devices.items():
        leaves = innermost(events)
        compute = [(s, e) for s, e, op in leaves if not COLLECTIVE.match(op)]
        _, gaps = union(compute + [(w0, w0), (w1, w1)])
        starts = [g[0] for g in gaps]
        for s, e, op in leaves + trace.async_ops.get(n, []):
            if not COLLECTIVE.match(op):
                continue
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            ns = 0
            while i < len(gaps) and gaps[i][0] < e:
                ns += max(0, min(e, gaps[i][1]) - max(s, gaps[i][0]))
                i += 1
            if ns:
                path = table.get(op, "")
                key = ("/".join(scopes_of(path)) or "(no scope)") \
                    + " " + pass_of(path)
                out[key] += ns / 1e9 / len(trace.devices)
    return dict(out)
