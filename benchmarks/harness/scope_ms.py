"""Milliseconds a step by block: the device's step under the names the
program gave it. The readers of ffn_ms, atten_dense_ms, kv_write_ms (each
with its .lat and .tput forms), moe_dispatch_ms, optimizer_ms and
unscoped_collective_ms share this, and the note device_ms_by_scope.

The program declares one tree of device scopes,
`lingvo_tpu.observe.schema.DEVICE_SCOPES` (name -> (parent, what it holds)),
and enters every `jax.named_scope` through it. This module keeps no copy of
the names: it reads that tree, attributes each device op of the traced steps
to the innermost declared scope in its `op_name` (spans.OfRun: the first
device's op self times, `op_name` from the module's HloProto), rolls children
up into their parents by the declared parent, and divides by the number of
step programs in the window. A share of a moving step misleads (every share
of unchanged work reads higher over a shorter step); milliseconds a step stay
put when a neighbour shrinks.

What the program did not name stays `_unscoped` and is classed by opcode:
the compiler's own all-gathers, layout copies and the scan's stacking
`dynamic-update-slice` carry no op of the program's.

A program without the registry (the parent of the PR that brought it) gives
every reader here None. With the registry a reader returns a number, 0.0
where a declared scope has no op in the cell.
"""

from __future__ import annotations

import json
import re

from benchmarks.harness import spans
from benchmarks.harness import xplane

UNSCOPED = "_unscoped"
OTHER = "other"
# what an unscoped op is classed as, by its opcode (a fusion by the
# instruction's name, which XLA makes of the opcodes it fused)
OPCODE_CLASSES = ("all-gather", "all-reduce", "reduce-scatter", "collective",
                  "copy", "dynamic-update-slice", "dynamic-slice", OTHER)
# `collective`: what the first three do not say (the chip's compiler wraps an
# async one as a fusion named `async-collective-start`; collective-permute)
_COLLECTIVES = OPCODE_CLASSES[:4]
# the scan over layers slices stacks that a mesh shards, and XLA turns those
# slices into FSDP's gathers: they carry the scan's scope and no layer's
_SCAN = "layer_scan"
_KEPT = 8        # unscoped ops the note lists by name


def Registry():
  """The program's DEVICE_SCOPES, None where it declares none."""
  try:
    from lingvo_tpu.observe import schema
  except ImportError:
    return None
  return getattr(schema, "DEVICE_SCOPES", None)


def _Pattern(scopes):
  """A scope is a path segment of op_name, bare or inside the brackets a
  transform puts round it ('jvp(atten)/transpose(jvp(qkv_proj))/dot')."""
  names = sorted(scopes, key=len, reverse=True)
  return re.compile(r"(?:^|[/(])(" + "|".join(map(re.escape, names))
                    + r")(?=[/)]|$)")


def ScopeOf(op_name: str, pattern) -> str:
  """The innermost declared scope in an op_name, UNSCOPED where none."""
  found = pattern.findall(op_name or "")
  return found[-1] if found else UNSCOPED


def OpcodeClass(short: str) -> str:
  """One of OPCODE_CLASSES for a device op's short name
  ('<opcode> %<instruction> <type>', xplane.ShortName)."""
  opcode = xplane.Opcode(short)
  where = short if opcode == "fusion" else opcode
  for cls in OPCODE_CLASSES[:-1]:
    if cls in where:
      return cls
  return OTHER


def Tree(ops, w0: float, w1: float, steps: int, scopes: dict) -> dict:
  """ops: [[short name, start_ns, dur_ns, op_name], ...] of one device;
  [w0, w1] the window of `steps` whole step programs; scopes: the declared
  tree. Milliseconds a step:
    scopes {name: {parent, self_ms, ms (with its children), kernel_ms (the
      Pallas kernels among its own ops), collective_ms (the collectives
      among them)}} for every declared scope,
    unscoped_by_opcode {class: ms}, unscoped_ms, sum_ms (every op's self
      time: the scopes' self_ms and unscoped_ms add up to it),
    largest_unnamed {name, ms}: the largest single figure nothing names,
      `_unscoped/other` or the own time, kernels apart, of a scope that
      holds more than its name tells: one whose declared children hold ops
      here (what lies under it in none of them) or one that a kernel is
      named after (what surrounds the kernel),
    unscoped_ops [[short name, ms, opcode class], ...], the largest."""
  pattern = _Pattern(scopes)
  per = 1e-6 / max(steps, 1)
  inside = [((o[0], o[3]), max(o[1], w0), min(o[1] + o[2], w1) - max(o[1], w0))
            for o in ops if o[1] + o[2] > w0 and o[1] < w1]
  self_ms = dict.fromkeys(scopes, 0.0)
  kernel_ms = dict.fromkeys(scopes, 0.0)
  collective_ms = dict.fromkeys(scopes, 0.0)
  by_class = dict.fromkeys(OPCODE_CLASSES, 0.0)
  unscoped_ops: dict[str, float] = {}
  scope_of: dict[tuple, str] = {}
  total = 0.0
  for key, _, self_d in xplane.SelfTimes(inside):
    if key not in scope_of:
      scope_of[key] = ScopeOf(key[1], pattern)
    scope, ms = scope_of[key], self_d * per
    total += ms
    if scope == UNSCOPED:
      by_class[OpcodeClass(key[0])] += ms
      unscoped_ops[key[0]] = unscoped_ops.get(key[0], 0.0) + ms
      continue
    self_ms[scope] += ms
    if xplane.Opcode(key[0]) == xplane.KERNEL:
      kernel_ms[scope] += ms
    elif OpcodeClass(key[0]) in _COLLECTIVES:
      collective_ms[scope] += ms
  children: dict[str, list] = {name: [] for name in scopes}
  for name, (parent, _) in scopes.items():
    if parent is not None:
      children[parent].append(name)

  def _Rolled(name):
    return self_ms[name] + sum(_Rolled(c) for c in children[name])

  rolled = {name: _Rolled(name) for name in scopes}
  unnamed = {UNSCOPED + "/" + OTHER: by_class[OTHER]}
  for name in scopes:
    if kernel_ms[name] > 0 or any(rolled[c] > 0 for c in children[name]):
      unnamed[name + " (own)"] = self_ms[name] - kernel_ms[name]
  worst = max(unnamed.items(), key=lambda kv: kv[1])
  return {
      "scopes": {name: {"parent": scopes[name][0], "self_ms": self_ms[name],
                        "ms": rolled[name], "kernel_ms": kernel_ms[name],
                        "collective_ms": collective_ms[name]}
                 for name in scopes},
      "unscoped_by_opcode": by_class,
      "unscoped_ms": sum(by_class.values()),
      "sum_ms": total,
      "largest_unnamed": {"name": worst[0], "ms": worst[1]},
      "unscoped_ops": [[xplane.SafeName(n, 96), ms, OpcodeClass(n)]
                       for n, ms in sorted(unscoped_ops.items(),
                                           key=lambda kv: -kv[1])[:_KEPT]]}


_memo: dict = {}


def ByScope(run):
  """Tree() of the traced run's step programs, None where the program
  declares no scopes. Prints note device_ms_by_scope, once a run: step_ms,
  the first device's busy ms a step, the scopes that hold time, the
  unscoped classes, and the largest unnamed figure with its share of the
  step."""
  scopes = Registry()
  if scopes is None:
    return None
  step = run["trace_step"]
  w0, w1 = step["window"]
  key = (w0, w1, step["count"])
  if key not in _memo:
    ops = spans.OfRun(run)["ops"]
    tree = Tree(ops, w0, w1, step["count"], scopes)
    step_ms = 1e3 * step["mean_s"]
    busy = spans._FirstDeviceBusy(ops, w0, w1)
    tree["step_ms"] = step_ms
    tree["busy_ms"] = sum(e - s for s, e in busy) * 1e-6 / step["count"]
    tree["largest_unnamed"]["share_of_step"] = (
        100.0 * tree["largest_unnamed"]["ms"] / step_ms)
    _memo.clear()
    _memo[key] = tree
    print(json.dumps({"note": "device_ms_by_scope", "value": dict(
        tree, steps=step["count"], scopes={
            k: v for k, v in tree["scopes"].items() if v["ms"] > 0})}),
          flush=True)
  return _memo[key]


def _Under(tree, name):
  """`name` and every declared descendant of it."""
  if name not in tree["scopes"]:
    return []
  out = [name]
  for child, v in tree["scopes"].items():
    if v["parent"] == name:
      out += _Under(tree, child)
  return out


def Rolled(run, *names):
  """The summed ms a step of the named scopes, each with its children."""
  tree = ByScope(run)
  if tree is None:
    return None
  return sum(tree["scopes"][n]["ms"] for n in names if n in tree["scopes"])


# what `atten` holds that has a reader of its own: the page write, the
# attend and scan kernels with what surrounds them
_ATTEN_APART = ("kv_write", "ragged_attend", "diff_attend", "ssm_scan")


def AttenDenseMs(run):
  """`atten` with its children, less _ATTEN_APART and every Pallas kernel
  left under it (flash attention is named `atten` on one chip and runs
  under `shard_map` inside it on a mesh): projections, rotary, the mixers'
  convolution and the block's own remainder."""
  tree = ByScope(run)
  if tree is None:
    return None
  apart = {n for a in _ATTEN_APART for n in _Under(tree, a)}
  return sum(tree["scopes"][n]["self_ms"] - tree["scopes"][n]["kernel_ms"]
             for n in _Under(tree, "atten") if n not in apart)


def UnscopedCollectiveMs(run):
  """Collectives that no layer's block carries: under no scope of the
  program's, or under the scan's alone (_SCAN)."""
  tree = ByScope(run)
  if tree is None:
    return None
  return (sum(tree["unscoped_by_opcode"][c] for c in _COLLECTIVES)
          + tree["scopes"].get(_SCAN, {}).get("collective_ms", 0.0))
