"""Count a round's kernels for a DESCRIBED v5e, without a chip.

The TPU compiler is installed beside jax and compiles for a chip that is
described and not attached (`on-chip-measurement` guide, section 2).  This
tool compiles a scan of `sim.step` — alone, with `kernels.check_safety`
folded into the carry as `runner._runner_body` folds it, or the audit by
itself — at a fleet's real size, and counts what the compiler made of each
catalogue scope (`raft_tpu.profiling.SCOPES`) inside the `while` body:
fusions, bare `reduce`s, copies by destination memory space, and the bytes
the fusions and copies write.  A round-body PR reads these before it asks
for a chip: a kernel count is exact and free, a time is neither.

    JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \\
        python3 tools/aot_round.py --groups 100000 --peers 5 --program round+audit

(19 s a compile at 100k x 5, about a minute at 1M x 3; `--program audit` is
seconds.)  Nothing runs: this says nothing about results or times, and no
number it prints is a device metric.  Only one process at a time may hold
libtpu unless `ALLOW_MULTIPLE_LIBTPU_LOAD=1` is set, so no test imports
this module's compile path; `tests/test_aot_round.py` holds the parser to a
stored snippet.
"""
import argparse
import json
import os
import re
import sys
import time
from typing import Collection, Dict, Iterable, List, NamedTuple, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}

# `  %name = <shape> opcode(operands), attrs` — the shape is one array
# (`s32[3,100000]{1,0:T(4,128)}`) or a tuple of them.
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*(?P<shape>\(.*?\)|\S+)\s+"
    r"(?P<op>[\w\-]+)\("
)
_ARRAY = re.compile(r"(?P<dt>[a-z]+\d*)\[(?P<dims>[\d,]*)\](?:\{(?P<layout>[^}]*)\})?")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?(?P<name>[\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_MEMORY_SPACE = re.compile(r"S\((\d+)\)")

_COPIES = ("copy", "copy-start", "slice-start")


class Instr(NamedTuple):
    name: str
    op: str
    shape: str
    op_name: str  # the jax name stack, "" where the compiler made the op


class ScopeCount(NamedTuple):
    fusions: int
    reduces: int
    copies: Dict[str, int]  # destination memory space -> count
    out_bytes: int  # written by the fusions, reduces and copies


def tiled_bytes(shape: str) -> int:
    """Bytes of one HLO shape as laid out: each array's last two dimensions
    rounded up to its `T(a,b)` tile (one dimension to `T(a)`), its sub-word
    `(c,1)` tiling read as packing c elements a word.  A tuple sums."""
    total = 0
    for m in _ARRAY.finditer(shape):
        size = _DTYPE_BYTES.get(m.group("dt"))
        if size is None:
            continue
        dims = [int(d) for d in m.group("dims").split(",") if d]
        layout = m.group("layout") or ""
        order = [int(d) for d in layout.split(":")[0].split(",") if d.strip().isdigit()]
        tile = re.search(r"T\(([\d,]+)\)", layout)
        if tile and dims and len(order) == len(dims):
            t = [int(x) for x in tile.group(1).split(",")]
            # minor_to_major: order[0] is the fastest dimension.
            for k, extent in enumerate(reversed(t)):
                if k < len(order):
                    d = order[k]
                    dims[d] = -(-dims[d] // extent) * extent
        n = 1
        for d in dims:
            n *= d
        total += n * size
    return total


def _elements(shape: str) -> List[str]:
    """The top-level elements of a tuple shape (one element for an array)."""
    if not shape.startswith("("):
        return [shape]
    out, depth, start = [], 0, 1
    for i, ch in enumerate(shape):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
            if depth == 0:
                out.append(shape[start:i])
        elif ch == "," and depth == 1:
            out.append(shape[start:i])
            start = i + 1
    return [e.strip() for e in out if e.strip()]


def written(ins: "Instr") -> str:
    """The part of an instruction's shape that it writes: an asynchronous
    copy's shape is a tuple of (destination, source, context) and an
    asynchronous slice's of ((operands), destination, context)."""
    parts = _elements(ins.shape)
    if ins.op == "copy-start":
        return parts[0]
    if ins.op == "slice-start":
        return parts[1] if len(parts) > 1 else parts[0]
    return ins.shape


def parse_computations(hlo: str) -> Dict[str, List[Instr]]:
    """Every computation of an HLO module's text, by name."""
    out: Dict[str, List[Instr]] = {}
    current: Optional[List[Instr]] = None
    for line in hlo.splitlines():
        head = _COMPUTATION.match(line)
        if head and "=" not in line.split("(")[0]:
            current = out.setdefault(head.group("name"), [])
            continue
        if line.strip() == "}":
            current = None
            continue
        if current is None:
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = _OP_NAME.search(line)
        current.append(
            Instr(m.group("name"), m.group("op"), m.group("shape"),
                  name.group(1) if name else "")
        )
    return out


def while_bodies(hlo: str) -> List[str]:
    """Names of the computations that are some `while`'s body."""
    return re.findall(r"\bwhile\(.*?body=%?([\w.\-]+)", hlo)


def scope_of(op_name: str, scopes: Collection[str]) -> str:
    """The INNERMOST catalogue scope on a name stack (`a/b/c`), or
    `(unnamed)` for a compiler-made op and `(unscoped)` for a named one
    under no catalogue scope."""
    if not op_name:
        return "(unnamed)"
    for part in reversed(op_name.split("/")):
        if part in scopes:
            return part
    return "(unscoped)"


def count_by_scope(
    instrs: Iterable[Instr], scopes: Iterable[str]
) -> Dict[str, ScopeCount]:
    scopes = frozenset(scopes)
    acc: Dict[str, list] = {}
    for ins in instrs:
        kind = (
            "fusion" if ins.op == "fusion"
            else "reduce" if ins.op in ("reduce", "reduce-window")
            else "copy" if ins.op in _COPIES
            else None
        )
        if kind is None:
            continue
        row = acc.setdefault(scope_of(ins.op_name, scopes), [0, 0, {}, 0])
        if kind == "fusion":
            row[0] += 1
        elif kind == "reduce":
            row[1] += 1
        else:
            space = _MEMORY_SPACE.search(written(ins))
            key = "S(%s)" % space.group(1) if space else "hbm"
            row[2][key] = row[2].get(key, 0) + 1
        row[3] += tiled_bytes(written(ins))
    return {k: ScopeCount(*v) for k, v in acc.items()}


def summarise(hlo: str, scopes: Iterable[str]) -> Tuple[str, Dict[str, ScopeCount]]:
    """(`while:<body>` or `entry`, per-scope counts) of the program's round:
    the largest `while` body, or the entry computation of a program that has
    no loop (the audit alone)."""
    comps = parse_computations(hlo)
    bodies = [b for b in while_bodies(hlo) if b in comps]
    if bodies:
        body = max(bodies, key=lambda b: len(comps[b]))
        return "while:" + body, count_by_scope(comps[body], scopes)
    entry = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
    name = entry.group(1) if entry else max(comps, key=lambda c: len(comps[c]))
    return "entry", count_by_scope(comps[name], scopes)


def total(counts: Dict[str, ScopeCount]) -> ScopeCount:
    copies: Dict[str, int] = {}
    for c in counts.values():
        for k, v in c.copies.items():
            copies[k] = copies.get(k, 0) + v
    return ScopeCount(
        sum(c.fusions for c in counts.values()),
        sum(c.reduces for c in counts.values()),
        copies,
        sum(c.out_bytes for c in counts.values()),
    )


def build_program(args: argparse.Namespace):
    """(function, example arguments as shapes) of the program `args` names:
    the audit alone, or `args.rounds` scanned rounds of `sim.step` under a
    link plane with or without the audit in the carry."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels, sim

    cfg = sim.SimConfig(
        n_groups=args.groups, n_peers=args.peers,
        election_tick=20, heartbeat_tick=2, collect_health=True,
        check_quorum=not args.stock, pre_vote=not args.stock,
        lease_read=not args.stock,
    )
    P, G = args.peers, args.groups
    st = jax.eval_shape(lambda: sim.init_state(cfg))
    hl = jax.eval_shape(lambda: sim.init_health(cfg))
    crashed = jax.ShapeDtypeStruct((P, G), jnp.bool_)
    link = jax.ShapeDtypeStruct((P, P, G), jnp.bool_)
    append = jax.ShapeDtypeStruct((G,), jnp.int32)

    def audit(st2, prev, crashed):
        # runner._runner_body's call, the two lease slots left out (their
        # holder mask is the client plan's, which this scan does not carry).
        return kernels.check_safety(
            st2.state, st2.term, st2.commit, st2.last_index, st2.agree,
            prev.commit,
            voter_mask=st2.voter_mask, outgoing_mask=st2.outgoing_mask,
            matched=st2.matched, crashed=crashed,
            prev_voter_mask=prev.voter_mask,
            prev_outgoing_mask=prev.outgoing_mask,
        )

    if args.program == "audit":
        return audit, (st, st, crashed)
    with_audit = args.program == "round+audit"

    def program(st, hl, crashed, link, append):
        def body(carry, _):
            st, hl, safety = carry
            st2, hl2 = sim.step(cfg, st, crashed, append, health=hl, link=link)
            if with_audit:
                safety = safety + audit(st2, st, crashed)
            return (st2, hl2, safety), ()
        safety = jnp.zeros((kernels.N_SAFETY,), jnp.int32)
        (st, hl, safety), _ = jax.lax.scan(
            body, (st, hl, safety), None, length=args.rounds
        )
        return st, hl, safety

    return program, (st, hl, crashed, link, append)


def _compile(args: argparse.Namespace) -> str:
    """The program's text as compiled for the described chip.  Everything
    that touches jax or libtpu is in here and in build_program, so importing
    this module loads neither."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    # A described-device compile is written to the persistent cache but can
    # never be read back without the chip: keep it out.
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    one_chip = SingleDeviceSharding(topo.devices[0])
    program, shapes = build_program(args)
    shapes = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes
    )
    return jax.jit(program).lower(*shapes).compile().as_text()


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--groups", type=int, default=100000)
    ap.add_argument("--peers", type=int, default=5)
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--program", choices=("round", "round+audit", "audit"),
                    default="round+audit")
    ap.add_argument("--stock", action="store_true",
                    help="raft-rs's default Config: the three damping flags off (round.linked)")
    ap.add_argument("--topology", default="v5e:2x2")
    ap.add_argument("--hlo", help="also write the compiled text here")
    ap.add_argument("--json", action="store_true", help="one JSON object instead of the table")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from raft_tpu import profiling

    t0 = time.time()
    hlo = _compile(args)
    seconds = time.time() - t0
    if args.hlo:
        with open(args.hlo, "w", encoding="utf-8") as f:
            f.write(hlo)
    where, counts = summarise(hlo, profiling.SCOPES)
    whole = total(counts)
    if args.json:
        print(json.dumps({
            "program": args.program, "groups": args.groups, "peers": args.peers,
            "stock": args.stock, "where": where, "compile_s": round(seconds, 1),
            "total": whole._asdict(),
            "scopes": {k: v._asdict() for k, v in sorted(counts.items())},
        }))
        return 0
    print("%s at %d x %d (%s), compiled for %s in %.1f s; counted in %s" % (
        args.program, args.groups, args.peers,
        "stock" if args.stock else "cq+pv+lease", args.topology, seconds, where))
    print("%-22s %8s %8s %-18s %12s" % ("scope", "fusions", "reduces", "copies", "out MB"))
    for name, c in sorted(counts.items(), key=lambda kv: -kv[1].out_bytes) + [("TOTAL", whole)]:
        copies = " ".join("%s:%d" % kv for kv in sorted(c.copies.items())) or "-"
        print("%-22s %8d %8d %-18s %12.2f" % (name, c.fusions, c.reduces, copies, c.out_bytes / 1e6))
    return 0


if __name__ == "__main__":
    sys.exit(main())
