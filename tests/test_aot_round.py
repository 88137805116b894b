"""`tools/aot_round.py`'s reading of a compiled program's text, on a stored
snippet (ISSUE 52).  The tool's compile path needs libtpu, which one process
at a time may hold, so no test runs it: this file imports the module (which
loads neither jax nor libtpu) and holds the parser to lines cut from a real
described-v5e compile of the round at 100 000 x 5."""

from tools import aot_round

SCOPES = ("round.damped", "damped.tick", "safety_audit", "quorum_commit")

# A `while` body of six ops and its caller; the shapes are the compiler's own.
HLO = '''\
HloModule jit_program, is_scheduled=true

%fused_computation.7 (param_0.1: s32[5,100000]) -> s32[5,100000] {
  %param_0.1 = s32[5,100000]{1,0:T(8,128)} parameter(0)
  %copy.9 = s32[5,100000]{1,0:T(8,128)} copy(%param_0.1)
  ROOT %add.3 = s32[5,100000]{1,0:T(8,128)} add(%copy.9, %copy.9)
}

%body.1 (arg: (s32[], s32[5,100000], s32[5,5,100000])) -> (s32[], s32[5,100000], s32[5,5,100000]) {
  %arg = (s32[]{:T(128)}, s32[5,100000]{1,0:T(8,128)}, s32[5,5,100000]{2,1,0:T(8,128)}) parameter(0)
  %gte.1 = s32[5,100000]{1,0:T(8,128)} get-tuple-element(%arg), index=1
  %gte.2 = s32[5,5,100000]{2,1,0:T(8,128)} get-tuple-element(%arg), index=2
  %fusion.2 = (s32[5,100000]{1,0:T(8,128)S(1)}, s32[5,100000]{1,0:T(8,128)S(1)}) fusion(%gte.2), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(program)/while/body/safety_audit/quorum_commit/reduce" stack_frame_id=7}
  %select_reduce_fusion = s32[100000]{0:T(1024)S(1)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(program)/while/body/safety_audit/reduce" stack_frame_id=9}
  %reduce_max.14 = s32[100000]{0:T(1024)} reduce(%gte.1, %gte.1), dimensions={0}, to_apply=%fused_computation.7, metadata={op_name="jit(program)/while/body/safety_audit/reduce_max" stack_frame_id=11}
  %copy-start.3 = (pred[5,100000]{1,0:T(8,128)(4,1)S(1)}, pred[5,100000]{1,0:T(8,128)(4,1)}, u32[]{:S(2)}) copy-start(%gte.1)
  %slice-start.4 = ((s32[3,1000000]{1,0:T(4,128)}), s32[3,250112]{1,0:T(4,128)S(1)}, s32[]{:S(2)}) slice-start(%gte.1), slice={[0:3], [0:250112]}
  %copy.225 = pred[5,5,100000]{2,1,0:T(8,128)(4,1)} copy(%gte.2)
  %fusion.5 = s32[5,5,100000]{2,1,0:T(8,128)} fusion(%gte.2), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(program)/while/body/closed_call/round/round.damped/damped.tick/or" stack_frame_id=56}
  %fusion.6 = s32[]{:T(128)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(program)/while/body/add" stack_frame_id=3}
  ROOT %tuple.1 = (s32[]{:T(128)}, s32[5,100000]{1,0:T(8,128)}, s32[5,5,100000]{2,1,0:T(8,128)}) tuple(%fusion.6, %gte.1, %fusion.5)
}

%cond.1 (arg.1: (s32[], s32[5,100000], s32[5,5,100000])) -> pred[] {
  %arg.1 = (s32[]{:T(128)}, s32[5,100000]{1,0:T(8,128)}, s32[5,5,100000]{2,1,0:T(8,128)}) parameter(0)
  ROOT %lt = pred[]{:T(512)} constant(true)
}

ENTRY %main.3 (p0: s32[5,100000], p1: s32[5,5,100000]) -> s32[5,100000] {
  %p0 = s32[5,100000]{1,0:T(8,128)} parameter(0)
  %p1 = s32[5,5,100000]{2,1,0:T(8,128)} parameter(1)
  %zero = s32[]{:T(128)} constant(0)
  %init = (s32[]{:T(128)}, s32[5,100000]{1,0:T(8,128)}, s32[5,5,100000]{2,1,0:T(8,128)}) tuple(%zero, %p0, %p1)
  %while.4 = (s32[]{:T(128)}, s32[5,100000]{1,0:T(8,128)S(1)}, s32[5,5,100000]{2,1,0:T(8,128)}) while(%init), condition=%cond.1, body=%body.1
  %fusion.9 = s32[5,100000]{1,0:T(8,128)} fusion(%p0), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(program)/safety_audit/not"}
  ROOT %out = s32[5,100000]{1,0:T(8,128)} get-tuple-element(%while.4), index=1
}
'''


def test_tiled_bytes_pads_to_the_tile():
    # [5, G] int32 under T(8,128): 8 sublanes held for 5 rows
    assert aot_round.tiled_bytes("s32[5,100000]{1,0:T(8,128)}") == 8 * 100096 * 4
    # pred packs four a word under T(4,128)(4,1): a byte an element, rows 3 -> 4
    assert aot_round.tiled_bytes("pred[3,1000000]{1,0:T(4,128)(4,1)}") == 4 * 1000064
    # the tile follows minor_to_major, not the dimension order
    assert aot_round.tiled_bytes("s32[3,1000000,3]{1,0,2:T(4,128)S(1)}") == 3 * 4 * 1000064 * 4
    assert aot_round.tiled_bytes("s32[100000]{0:T(1024)}") == 100352 * 4
    assert aot_round.tiled_bytes("s32[]{:T(128)}") == 4


def test_the_round_is_the_while_body_counted_by_innermost_scope():
    where, counts = aot_round.summarise(HLO, SCOPES)
    assert where == "while:body.1"
    # the entry's own fusion and the fused computation's inner copy are not
    # the round's
    assert sum(c.fusions for c in counts.values()) == 4
    audit, quorum = counts["safety_audit"], counts["quorum_commit"]
    assert (quorum.fusions, quorum.reduces) == (1, 0)  # the inner name wins
    assert quorum.out_bytes == 2 * 8 * 100096 * 4  # a multi-output fusion writes both
    assert (audit.fusions, audit.reduces) == (1, 1)
    assert audit.out_bytes == 2 * 100352 * 4
    assert counts["damped.tick"].fusions == 1
    assert counts["(unscoped)"].fusions == 1  # named, under no catalogue scope


def test_copies_are_the_compilers_and_counted_by_where_they_land():
    _, counts = aot_round.summarise(HLO, SCOPES)
    unnamed = counts["(unnamed)"]
    assert unnamed.copies == {"S(1)": 2, "hbm": 1}
    # an asynchronous copy writes its first element, a slice its second
    assert unnamed.out_bytes == (
        8 * 100096 + aot_round.tiled_bytes("s32[3,250112]{1,0:T(4,128)S(1)}")
        + 5 * 8 * 100096
    )
    whole = aot_round.total(counts)
    assert (whole.fusions, whole.reduces) == (4, 1)
    assert whole.copies == {"S(1)": 2, "hbm": 1}


def test_a_program_without_a_loop_is_counted_at_its_entry():
    entry_only = HLO[HLO.index("ENTRY"):].replace(
        "while(%init), condition=%cond.1, body=%body.1", "tuple(%zero, %p0, %p1)"
    )
    where, counts = aot_round.summarise(entry_only, SCOPES)
    assert where == "entry"
    assert counts["safety_audit"].fusions == 1
