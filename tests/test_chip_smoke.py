"""chip_smoke.py's phases at a tiny size on the CPU mesh.

The script itself refuses to run off the TPU; its phase functions are
importable so this test can drive the same server, ingest, dict, client
and check code on the 8-device CPU mesh: every planted PSK must be
cracked and accepted, and the found set must equal the oracle's.
"""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke as cs  # noqa: E402


def test_main_fails_off_chip(capsys):
    assert cs.main([]) != 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last)["ok"] is False


def test_phases_crack_every_planted_psk(tmp_path):
    rules = "\n".join([":", "c", "$1", cs.PLANTED_RULE])
    fx = cs.build_fixture(seed=3, words_a=300, words_b=40, rules_text=rules)
    seed_dir = str(tmp_path / "seed")
    nets, want = cs.seed_server(seed_dir, fx)
    assert nets == 8 and len(want) == 8
    # lockstep: on the forced-8-device CPU mesh the stream path runs 8
    # serialized single-device programs, several times slower here
    r = cs.smoke_run(str(tmp_path / "run"), seed_dir, fx, want,
                     batch_size=64, device_streams="off")
    assert r["kinds"] == {"pmkid": 2, "k1": 2, "k2": 2, "k3": 2}
    assert len(r["found"]) == 8
    assert {psk for _, psk in r["found"]} == set(fx["psk"].values())
    assert r["rules_on_device"] > 0
    assert len(r["unit_s"]) == cs.UNITS


def test_fixture_plants_psks_where_promised():
    fx = cs.build_fixture(seed=5, words_a=5000, words_b=100)
    psk1 = fx["psk"][cs.ESSID_1]
    assert psk1 in fx["dict_a"][-1000:]
    assert fx["psk"][cs.ESSID_2] not in fx["dict_b"]
    assert not any(b"-" in w for w in fx["dict_b"])
    with pytest.raises(ValueError):
        cs.build_fixture(words_a=10, words_b=10, rules_text=":\nc")


def test_run_modes_give_each_chip_the_one_chip_batch():
    assert cs.run_modes(1) == [("1 chip", "auto", cs.CHIP_BATCH)]
    modes = {streams: batch for _, streams, batch in cs.run_modes(4)}
    # a stream owns a chip; lockstep splits its batch over the 4 chips
    assert modes == {"on": cs.CHIP_BATCH, "off": 4 * cs.CHIP_BATCH}
    assert cs.PLANTED_RULE in cs.MESH_RULES and len(cs.MESH_RULES) == 8
