"""The traffic generator: seeded, in the program's packed layout, and the
mix it says it is."""

import json
import os

import numpy as np
import pytest

from benchmark import check, traffic


def gen(mix, G=4096, P=5, seed=3_000_000_019):
    return traffic.generate(traffic.load_mix(mix), G, P, seed, name=mix)


@pytest.mark.parametrize("mix", ["serve", "outage", "load"])
def test_same_seed_same_arrays_other_seed_other_arrays(mix):
    a, b, c = gen(mix), gen(mix), gen(mix, seed=11)
    for x, y in zip(a, b):
        if isinstance(x, np.ndarray):
            assert np.array_equal(x, y)
        else:
            assert x == y
    if mix != "load":
        assert not np.array_equal(a.read_fire_packed, c.read_fire_packed)
        assert a.read_fire_packed.shape == c.read_fire_packed.shape
        assert abs(a.read_fires - c.read_fires) < 0.02 * a.read_fires  # same work


@pytest.mark.parametrize("G", [64, 100, 4096 + 7])
def test_packed_fire_words_are_the_programs_layout(G):
    import jax.numpy as jnp

    from raft_tpu.multiraft import kernels

    seg = gen("serve", G=G)
    mask = traffic.unpack_bits(seg.read_fire_packed, G)
    assert mask.shape == (seg.n_rounds, G) and int(mask.sum()) == seg.read_fires
    want = np.asarray(kernels.pack_bits_g(jnp.asarray(mask)))
    assert np.array_equal(seg.read_fire_packed, want)
    assert np.array_equal(traffic.pack_bits(mask), want)
    back = np.asarray(kernels.unpack_bits_g(jnp.asarray(seg.read_fire_packed), G))
    assert np.array_equal(back, mask)


def test_serve_is_95_5_and_leaves_most_regions_alone():
    seg = gen("serve", G=100_000)
    ops = seg.read_ops + seg.update_entries
    print(f"reads {seg.read_ops} updates {seg.update_entries} "
          f"share of regions touched per round {seg.touched_share:.4f} "
          f"fires {seg.read_fires} (coalesced from {seg.read_ops})")
    assert abs(seg.read_ops / ops - 0.95) < 0.01
    assert abs(ops / (seg.n_rounds * seg.n_groups) - 0.125) < 0.002
    assert 0.02 < seg.touched_share < 0.25
    assert seg.n_rounds == 24 and seg.split and seg.split_k == 8
    assert seg.read_mode.min() == seg.read_mode.max() == traffic.MODE_CODES["lease"]
    # Zipfian: the hottest region takes far more than its even share.
    assert seg.append.sum(axis=0).max() > 1000 * seg.update_entries / seg.n_groups / 10


def test_load_is_one_entry_per_region_per_round_and_no_reads():
    seg = gen("load", G=1000)
    assert seg.read_fires == 0 and not seg.read_fire_packed.any()
    assert seg.n_rounds == 2000 and seg.n_rounds % seg.split_k == 0  # no general tail block
    assert (seg.append == 1).all() and seg.update_entries == seg.n_rounds * 1000
    assert seg.write_batches == seg.n_rounds * 1000 and seg.touched_share == 1.0


def test_outage_rolls_a_crash_over_every_peer_then_cuts_one_off():
    seg = gen("outage", G=256, P=3)
    assert seg.n_rounds == 100 * 3 + 100 and not seg.split
    crashed, link = check.fault_rows(seg)
    for s in range(3):
        assert crashed[100 * s + 40:100 * s + 100, s].all()
        assert crashed[:, s].sum() == 60
    assert link[:340].all() and not crashed[300:].any()
    cut = link[340:]
    assert not cut[:, 0, 1].any() and not cut[:, 2, 0].any() and cut[:, 1, 2].all()
    from raft_tpu.multiraft import chaos

    plan = chaos.plan_from_dict(seg.chaos)  # the program's grammar takes it
    assert plan.n_rounds == seg.n_rounds


def test_sample_rows_match_the_unpacked_schedule():
    seg = gen("serve", G=512)
    gids = check.pick_sample(seg, 5, 6)
    assert len(set(gids.tolist())) == 6
    fire, mode, append = traffic.sample_rows(seg, gids)
    mask = traffic.unpack_bits(seg.read_fire_packed, 512)
    assert np.array_equal(fire, mask[:, gids])
    assert np.array_equal(append, seg.append[seg.phase_of_round][:, gids])
    assert mode.shape == fire.shape


# --- a mix whose schedule changes memberships ---------------------------------

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MOVE = [  # store 1's replica moves to store 4 ...
    {"rounds": 8},
    {"rounds": 8, "op": {"add_learner": 4}, "groups": {"mod": 4, "eq": 1}},
    {"rounds": 8, "op": {"enter_joint": [{"add": 4}, {"remove": 1}]}, "groups": {"mod": 4, "eq": 1}},
    {"rounds": 40, "op": {"leave_joint": True}, "groups": {"mod": 4, "eq": 1}, "append": 2},
]
BACK = [  # ... and back
    {"rounds": 8, "op": {"add_learner": 1}, "groups": {"mod": 4, "eq": 1}},
    {"rounds": 8, "op": {"enter_joint": [{"add": 1}, {"remove": 4}]}, "groups": {"mod": 4, "eq": 1}},
    {"rounds": 40, "op": {"leave_joint": True}, "groups": {"mod": 4, "eq": 1}},
]


def churn_mix(phases, **more):
    with open(os.path.join(DATA, "churn.json"), encoding="utf-8") as f:
        mix = json.load(f)
    return {**mix, "reconfig": {"phases": phases}, **more}


def test_the_reconfig_key_passes_through_in_the_programs_grammar():
    from raft_tpu.multiraft import reconfig

    G = 256
    seg = traffic.generate(churn_mix(MOVE + BACK), G, 5, 7, name="a.cell", voters=[1, 2, 3])
    assert seg.n_rounds == 120 and seg.chaos is None and not seg.split
    assert seg.reconfig == {"name": "a.cell", "peers": 5, "voters": [1, 2, 3], "learners": [],
                            "phases": MOVE + BACK}
    assert seg.conf_ops == 6 * (G // 4)
    plan = reconfig.plan_from_dict(seg.reconfig)  # the program's grammar takes it
    assert plan.n_rounds == seg.n_rounds and plan.voters == [1, 2, 3]
    compiled = reconfig.compile_plan(plan, G)
    assert int(compiled.n_ops.sum()) == seg.conf_ops
    # The reconfig phase's load goes to every region, on top of the client's.
    fire, mode, append = traffic.sample_rows(seg, np.array([0, 1]))
    client = seg.append[:, [0, 1]][seg.phase_of_round]
    assert np.array_equal(append[24:64] - client[24:64], np.full((40, 2), 2))
    assert np.array_equal(append[:24], client[:24]) and np.array_equal(append[64:], client[64:])
    plain = traffic.generate(churn_mix(MOVE + BACK, reconfig=None, segment_rounds=120), G, 5, 7)
    assert seg.update_entries == plain.update_entries + 40 * 2 * G
    assert seg.write_batches > plain.write_batches and plain.conf_ops == 0
    assert np.array_equal(seg.read_fire_packed, plain.read_fire_packed)  # same draws


def test_a_mix_without_the_key_means_what_it_meant():
    seg = gen("outage", G=256)
    assert seg.reconfig is None and seg.conf_ops == 0


def test_chaos_and_reconfig_schedules_must_agree_in_length():
    chaos = {"then": [{"rounds": 60}, {"rounds": 59, "crash": [1]}]}
    with pytest.raises(ValueError, match="differ in length.*119.*120|differ in length.*120.*119"):
        traffic.generate(churn_mix(MOVE + BACK, chaos=chaos), 64, 5, 7, voters=[1, 2, 3])
    chaos["then"][1]["rounds"] = 60
    seg = traffic.generate(churn_mix(MOVE + BACK, chaos=chaos), 64, 5, 7, voters=[1, 2, 3])
    assert seg.n_rounds == 120 and seg.chaos["phases"][1]["crash"] == [1]


def test_a_schedule_that_does_not_end_where_it_began_is_refused():
    with pytest.raises(ValueError) as e:
        traffic.generate(churn_mix(MOVE), 64, 5, 7, name="a.cell", voters=[1, 2, 3])
    text = str(e.value)
    assert "16 groups" in text and "phases [1, 2, 3]" in text and "group 1)" in text
    assert "end at voters [2, 3, 4]" in text and "not at the configuration's voters [1, 2, 3]" in text
    # ... and one that ends inside a joint window, and one the Changer refuses.
    with pytest.raises(ValueError, match=r"outgoing \[2, 3, 4\]"):
        traffic.generate(churn_mix(MOVE + BACK[:2]), 64, 5, 7, voters=[1, 2, 3])
    with pytest.raises(ValueError, match="phase 1.*refused"):
        traffic.generate(churn_mix([{"rounds": 8}, {"rounds": 8, "op": {"leave_joint": True}}]),
                         64, 5, 7, voters=[1, 2, 3])
    # The same schedule from another starting membership: store 4 holds a voter already.
    with pytest.raises(ValueError):
        traffic.generate(churn_mix(MOVE + BACK), 64, 5, 7, voters=[2, 3, 4])


def test_classes_of_groups_are_walked_apart():
    other = [dict(ph, groups={"mod": 4, "eq": 3}) if "op" in ph else ph for ph in MOVE]
    with pytest.raises(ValueError, match=r"phases \[9, 10, 11\].*group 3\)"):
        traffic.generate(churn_mix(MOVE + BACK + [{"rounds": 8}] + other), 64, 5, 7,
                         voters=[1, 2, 3])


def test_at_least_half_of_the_sample_are_groups_the_schedule_changes():
    seg = traffic.generate(churn_mix(MOVE + BACK), 4096, 5, 7, voters=[1, 2, 3])
    for seed in range(8):
        gids = check.pick_sample(seg, seed, 6)
        assert len(set(gids.tolist())) == 6
        assert (gids % 4 == 1).sum() >= 3
    chains = check.conf_chains(seg, np.array([1, 2]))
    steps, starts = chains[0]
    assert starts == [8, 16, 24, 64, 72, 80] and chains[1] == ([], [])
    assert [sorted(s.voters) for s in steps] == [[1, 2, 3], [2, 3, 4], [2, 3, 4],
                                                 [2, 3, 4], [1, 2, 3], [1, 2, 3]]
    assert [sorted(s.outgoing) for s in steps][1::3] == [[1, 2, 3], [2, 3, 4]]
