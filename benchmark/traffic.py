"""The one general traffic generator: `traffic/<mix>.json` + (G, P, seed)
-> one *segment*, the schedule the run replays call after call.

A mix is data only (see README.md "Adding a traffic mix").  Its keys:

  segment_rounds | segment_rounds_per_peer   length of the segment
  phase_rounds            rounds per client phase (update loads are drawn
                          once per phase; equal to split_k so a fused block
                          spans one phase)
  ops_per_round_per_group operations offered per round, as a share of G
                          (open loop: offered whatever the fleet's state)
  read_share              share of the operations that are reads
  read_mode               "lease" | "safe"
  distribution            {"kind": "zipfian", "constant": c} — YCSB's
                          Zipfian over the G regions, ranks scattered over
                          the region ids by a seeded permutation; or
                          {"kind": "every_region"} — every region takes the
                          same share (hashed keys)
  split                   whether run_reads is called with split=True
  counted_segments        optional, an integer >= 1, read by run.py and not by
                          the generator: the line's exact counts
                          (`attempted`, `failed`, the round counts inside the
                          two latencies) are taken over the first so many
                          timed segments, time over the whole window.  A mix
                          whose healthy share of failures is not 0 states it
                          (README.md, "Exact counts")
  chaos                   optional {"for_each_peer": [phase, ...], "then":
                          [phase, ...]}: chaos phases in the program's plan
                          grammar ("rounds", "crash", "partition"), the first
                          list repeated for peer s = 1..P with "@peer"
                          standing for s, the second appended once
  reconfig                optional {"phases": [phase, ...]}: a membership-
                          change schedule in the program's plan grammar
                          ("rounds", "op", "groups", "append"; the ops are
                          listed in reference/membership.py), its name,
                          peers, voters and learners filled in from the cell
                          and its configuration.  The segment is as long as
                          its phases (and as the chaos phases, where the mix
                          has both).  A mix is one segment replayed with
                          state carried over, so every class of groups has to
                          end, outside any joint window, at the
                          configuration's voters and learners: `generate`
                          walks each class through the reference's Changer
                          and refuses a schedule that does not

Operations of one kind on one region in one round coalesce: reads into one
read fire, updates into one append of n entries.  Reads are drawn as the
exact per-region marginal of n draws from the distribution (a region fires
with probability 1 - (1 - p)^n, independently), which costs one pass over
G per round instead of a search per operation; updates are n exact draws
per phase, applied in each round of the phase.
"""

from __future__ import annotations

import collections
import json
import os
from typing import NamedTuple, Optional, Tuple

import numpy as np

from .reference import membership

HERE = os.path.dirname(os.path.abspath(__file__))
MIX_DIR = os.path.join(HERE, "traffic")
MODE_CODES = {"safe": 1, "lease": 2}  # sim.READ_SAFE / sim.READ_LEASE


class Segment(NamedTuple):
    """Host arrays of one segment, in the layout of the program's
    `workload.CompiledClient`."""

    phase_of_round: np.ndarray  # int32[R]
    read_fire_packed: np.ndarray  # uint32[R, ceil(G/32)], bit j of word w = group 32w+j
    read_mode: np.ndarray  # int32[NPH, G]
    append: np.ndarray  # int32[NPH, G]
    chaos: Optional[dict]  # a chaos plan document, or None
    n_groups: int
    n_peers: int
    split: bool
    split_k: int
    read_fires: int  # fires in the segment (after coalescing)
    read_ops: int  # expected read operations offered (before coalescing)
    update_entries: int  # entries offered over the segment
    write_batches: int  # (group, round) pairs that offer an append
    touched_share: float  # mean share of regions with any operation in a round
    reconfig: Optional[dict] = None  # a reconfig plan document, or None
    conf_ops: int = 0  # (op, group) pairs the schedule enqueues in one segment

    @property
    def n_rounds(self) -> int:
        return int(self.phase_of_round.shape[0])


def load_mix(name: str) -> dict:
    path = os.path.join(MIX_DIR, f"{name}.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def pack_bits(mask: np.ndarray) -> np.ndarray:
    """bool[..., G] -> uint32[..., ceil(G/32)], little-endian bit order
    (the program's `kernels.pack_bits_g` layout)."""
    g = mask.shape[-1]
    pad = (-g) % 32
    if pad:
        mask = np.concatenate(
            [mask, np.zeros(mask.shape[:-1] + (pad,), bool)], axis=-1
        )
    by = np.packbits(mask, axis=-1, bitorder="little")
    return np.ascontiguousarray(by).view("<u4")


def unpack_bits(words: np.ndarray, g: int) -> np.ndarray:
    by = np.ascontiguousarray(words.astype("<u4")).view(np.uint8)
    return np.unpackbits(by, axis=-1, bitorder="little")[..., :g].astype(bool)


def region_weights(dist: dict, n_groups: int, rng) -> np.ndarray:
    """float64[G]: the probability that one operation goes to each region."""
    kind = dist["kind"]
    if kind == "every_region":
        return np.full(n_groups, 1.0 / n_groups)
    if kind == "zipfian":
        rank = np.arange(1, n_groups + 1, dtype=np.float64)
        w = rank ** -float(dist["constant"])
        w /= w.sum()
        out = np.empty(n_groups)
        out[rng.permutation(n_groups)] = w  # hot regions scattered
        return out
    raise ValueError(f"unknown distribution kind {kind!r}")


def segment_rounds(mix: dict, n_peers: int) -> int:
    lengths = {
        key: sum(int(ph["rounds"]) for ph in doc["phases"])
        for key, doc in (("chaos", chaos_document(mix, n_peers, "")),
                         ("reconfig", mix.get("reconfig")))
        if doc
    }
    if len(set(lengths.values())) > 1:
        raise ValueError(f"the mix's schedules differ in length: {lengths} rounds")
    if lengths:
        return next(iter(lengths.values()))
    return int(mix["segment_rounds"])


def chaos_document(mix: dict, n_peers: int, name: str) -> Optional[dict]:
    spec = mix.get("chaos")
    if not spec:
        return None
    def bind(ph: dict, s) -> dict:
        ph = dict(ph)
        if "crash" in ph:
            ph["crash"] = [s if p == "@peer" else int(p) for p in ph["crash"]]
        if "partition" in ph:
            ph["partition"] = [
                [s if p == "@peer" else int(p) for p in side]
                for side in ph["partition"]
            ]
        return ph

    phases = [
        bind(ph, s)
        for s in range(1, n_peers + 1)
        for ph in spec.get("for_each_peer", [])
    ]
    phases += [bind(ph, None) for ph in spec.get("then", [])]
    return {"name": name, "peers": n_peers, "phases": phases}


def reconfig_document(mix: dict, n_groups: int, n_peers: int, name: str,
                      voters, learners) -> Tuple[Optional[dict], int]:
    """(the mix's membership-change schedule as a plan document of the
    program's grammar, the (op, group) pairs it enqueues), or (None, 0).
    Refuses a schedule some class of groups does not end, outside a joint
    window, at `voters` / `learners`."""
    spec = mix.get("reconfig")
    if not spec:
        return None, 0
    phases = [dict(ph) for ph in spec["phases"]]
    home = (frozenset(voters), frozenset(), frozenset(learners), frozenset())
    conf_ops = 0
    for chain, groups in membership.classes(phases, n_groups).items():
        last = membership.walk(phases, chain, n_peers, voters, learners)[-1]
        if (last.voters, last.outgoing, last.learners, last.learners_next) != home:
            raise ValueError(
                f"reconfig schedule of {name!r}: the {int(groups.sum())} groups that "
                f"follow the ops of phases {list(chain)} (first: group "
                f"{int(np.flatnonzero(groups)[0])}) end at voters {sorted(last.voters)} "
                f"outgoing {sorted(last.outgoing)} learners {sorted(last.learners)} "
                f"learners_next {sorted(last.learners_next)}, not at the configuration's "
                f"voters {sorted(voters)} learners {sorted(learners)}: a mix is one "
                "segment replayed, so it has to end where it began"
            )
        conf_ops += len(chain) * int(groups.sum())
    if not conf_ops:
        raise ValueError(f"reconfig schedule of {name!r} has no op")
    doc = {"name": name, "peers": n_peers, "voters": sorted(voters),
           "learners": sorted(learners), "phases": phases}
    return doc, conf_ops


def conf_append_by_round(seg_reconfig: Optional[dict], n_rounds: int) -> np.ndarray:
    """int32[R]: the entries a reconfig phase offers every group each round."""
    out = np.zeros(n_rounds, np.int32)
    r = 0
    for ph in (seg_reconfig or {}).get("phases", []):
        out[r:r + int(ph["rounds"])] = int(ph.get("append", 0))
        r += int(ph["rounds"])
    return out


def generate(mix: dict, n_groups: int, n_peers: int, seed: int,
             name: str = "mix", voters=None, learners=()) -> Segment:
    """`voters` / `learners`: the configuration's membership (1-based peer
    slots; default every slot a voter), which a `reconfig` schedule starts
    from and has to return to."""
    G = n_groups
    R = segment_rounds(mix, n_peers)
    voters = list(range(1, n_peers + 1)) if voters is None else list(voters)
    reconfig, conf_ops = reconfig_document(mix, G, n_peers, name, voters, list(learners))
    pr = int(mix["phase_rounds"])
    nph = -(-R // pr)
    phase_of_round = (np.arange(R) // pr).astype(np.int32)
    rng = np.random.Generator(np.random.PCG64(int(seed)))
    p = region_weights(mix["distribution"], G, rng)

    ops = float(mix["ops_per_round_per_group"]) * G
    read_share = float(mix["read_share"])
    n_reads = ops * read_share  # read operations per round
    n_updates = ops * (1.0 - read_share)  # update entries per round

    words = (G + 31) // 32
    fire_packed = np.zeros((R, words), dtype="<u4")
    read_mode = np.zeros((nph, G), np.int32)
    fires = 0
    q_read = np.zeros(G)
    if n_reads > 0:
        read_mode[:] = MODE_CODES[mix["read_mode"]]
        # P(at least one of n draws lands on the region).
        q_read = -np.expm1(n_reads * np.log1p(-p))
        q32 = q_read.astype(np.float32)
        for r in range(R):
            mask = rng.random(G, dtype=np.float32) < q32
            fires += int(mask.sum())
            fire_packed[r] = pack_bits(mask)

    append = np.zeros((nph, G), np.int32)
    entries = batches = 0
    q_upd = np.zeros(G)
    if n_updates > 0:
        if mix["distribution"]["kind"] == "every_region":
            per = n_updates / G
            if per != int(per):
                raise ValueError("every_region needs a whole load per region")
            append[:] = int(per)
        else:
            cdf = np.cumsum(p)
            cdf[-1] = 1.0
            m = int(round(n_updates))
            for i in range(nph):
                hit = np.searchsorted(cdf, rng.random(m), side="right")
                append[i] = np.bincount(hit, minlength=G)[:G]
        q_upd = -np.expm1(n_updates * np.log1p(-p)) if n_updates < G else np.ones(G)
    # Rounds by (client phase, entries the reconfig phase adds for every group).
    extra = conf_append_by_round(reconfig, R)
    for (i, x), n in collections.Counter(zip(phase_of_round.tolist(), extra.tolist())).items():
        entries += n * (int(append[i].sum()) + x * G)
        batches += n * (G if x else int((append[i] > 0).sum()))

    touched = float(np.mean(1.0 - (1.0 - q_read) * (1.0 - q_upd)))
    loaded = float(np.mean(extra > 0))  # rounds whose reconfig phase loads every region
    touched = loaded + (1.0 - loaded) * touched
    return Segment(
        phase_of_round=phase_of_round,
        read_fire_packed=fire_packed,
        read_mode=read_mode,
        append=append,
        chaos=chaos_document(mix, n_peers, name),
        n_groups=G,
        n_peers=n_peers,
        split=bool(mix.get("split", False)),
        split_k=pr,
        read_fires=fires,
        read_ops=int(round(n_reads * R)),
        update_entries=entries,
        write_batches=batches,
        touched_share=touched,
        reconfig=reconfig,
        conf_ops=conf_ops,
    )


def sample_rows(seg: Segment, gids: np.ndarray):
    """(fire bool[R, n], mode int[R, n], append int[R, n]) of the sampled
    groups, gathered by phase: what the reference replays."""
    gids = np.asarray(gids)
    w, b = gids // 32, gids % 32
    fire = ((seg.read_fire_packed[:, w] >> b.astype(np.uint32)) & 1).astype(bool)
    ph = seg.phase_of_round
    append = seg.append[:, gids][ph] + conf_append_by_round(seg.reconfig, seg.n_rounds)[:, None]
    return fire, seg.read_mode[:, gids][ph], append
