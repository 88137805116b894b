"""The traffic generator: seeded, in the program's packed layout, and the
mix it says it is."""

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
