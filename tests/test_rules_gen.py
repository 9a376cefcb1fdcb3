"""Host-side candidate pipeline tests: rule engine, masks, generators."""

import gzip
import hashlib
import io

import pytest

from dwpa_tpu.gen import (
    DictStream,
    imei_candidates,
    luhn_check_digit,
    mask_keyspace,
    mask_words,
    md5_file,
    psk_candidates,
)
from dwpa_tpu.rules import RuleError, apply_rules, parse_rule, parse_rules


def apply(rule_text, word):
    return parse_rule(rule_text).apply(word)


@pytest.mark.parametrize(
    "rule,word,expected",
    [
        (":", b"pass", b"pass"),
        ("l", b"PaSS", b"pass"),
        ("u", b"pass", b"PASS"),
        ("c", b"passWORD", b"Password"),
        ("C", b"PassWord", b"pASSWORD"),
        ("t", b"PaSs", b"pAsS"),
        ("T0", b"pass", b"Pass"),
        ("T3", b"pass", b"pasS"),
        ("r", b"abcd", b"dcba"),
        ("d", b"ab", b"abab"),
        ("p2", b"ab", b"ababab"),
        ("f", b"abc", b"abccba"),
        ("{", b"abcd", b"bcda"),
        ("}", b"abcd", b"dabc"),
        ("$1", b"pass", b"pass1"),
        ("$1 $2 $3", b"pass", b"pass123"),
        ("^x", b"pass", b"xpass"),
        ("[", b"pass", b"ass"),
        ("]", b"pass", b"pas"),
        ("D1", b"pass", b"pss"),
        ("x13", b"abcdef", b"bcd"),
        ("O12", b"abcdef", b"adef"),
        ("o2X", b"abcd", b"abXd"),
        ("'3", b"abcdef", b"abc"),
        ("sab", b"banana", b"bbnbnb"),
        ("@a", b"banana", b"bnn"),
        ("z2", b"ab", b"aaab"),
        ("Z2", b"ab", b"abbb"),
        ("q", b"ab", b"aabb"),
        ("k", b"abcd", b"bacd"),
        ("K", b"abcd", b"abdc"),
        ("*03", b"abcd", b"dbca"),
        ("+0", b"abc", b"bbc"),
        ("-0", b"bbc", b"abc"),
        (".0", b"abc", b"bbc"),
        (",1", b"abc", b"aac"),
        ("y2", b"abcd", b"ababcd"),
        ("Y2", b"abcd", b"abcdcd"),
        ("T9", b"pass", b"pass"),  # out-of-range position: no-op
        ("u $! T0", b"pass", b"pASS!"),
    ],
)
def test_rule_semantics(rule, word, expected):
    assert apply(rule, word) == expected


def test_insert_arity():
    # 'i' takes position + single char
    assert apply("i2X", b"abcd") == b"abXcd"


def test_reject_rules():
    assert apply("<5", b"pass") == b"pass"
    assert apply("<4", b"pass") is None
    assert apply(">3", b"pass") == b"pass"
    assert apply(">4", b"pass") is None
    assert apply("_4", b"pass") == b"pass"
    assert apply("_5", b"pass") is None
    assert apply("!x", b"pass") == b"pass"
    assert apply("!a", b"pass") is None
    assert apply("/a", b"pass") == b"pass"
    assert apply("/x", b"pass") is None
    assert apply("(p", b"pass") == b"pass"
    assert apply(")s", b"pass") == b"pass"
    assert apply("=0p", b"pass") == b"pass"
    assert apply("=0q", b"pass") is None
    assert apply("%2s", b"pass") == b"pass"
    assert apply("%3s", b"pass") is None


def test_parse_rules_skips_bad_lines():
    rules = parse_rules(["# comment", "", "l", "Mbogus", "u"])
    assert [r.text for r in rules] == ["l", "u"]
    with pytest.raises(RuleError):
        parse_rules(["Mbogus"], on_error="raise")


def test_apply_rules_expansion_order():
    rules = parse_rules([":", "u", "$1"])
    out = list(apply_rules(rules, [b"ab", b"cd"]))
    assert out == [b"ab", b"AB", b"ab1", b"cd", b"CD", b"cd1"]


def test_mask_generator():
    assert mask_keyspace("?d?d") == 100
    words = list(mask_words("?d?d"))
    assert words[0] == b"00" and words[-1] == b"99" and len(words) == 100
    assert list(mask_words("a?dc", limit=2)) == [b"a0c", b"a1c"]
    # keyspace slicing lines up with full enumeration
    assert list(mask_words("?d?d", skip=42, limit=3)) == [b"42", b"43", b"44"]
    assert mask_keyspace("?d?d?d?d?d?d?d?d") == 10**8


def test_luhn():
    # classic Luhn example: 7992739871 -> check digit 3
    assert luhn_check_digit("7992739871") == 3
    for cand in imei_candidates("35294906", serial_range=(0, 10)):
        assert len(cand) == 8 and cand.isdigit()
    cands = list(imei_candidates("3529490612345"))
    assert len(cands) == 10  # one free digit


def test_psk_candidates():
    mac = bytes.fromhex("a0b1c2d3e4f5")
    cands = list(psk_candidates(b"MyNet-4521", mac_ap=mac))
    assert all(8 <= len(c) <= 63 for c in cands)
    assert len(cands) == len(set(cands))
    assert b"00004521" in cands  # embedded digit run, zero-padded
    assert b"a0b1c2d3e4f5" in cands  # full BSSID hex


def test_dict_stream(tmp_path):
    words = b"alpha\nbeta\n\ngamma\n"
    plain = tmp_path / "d.txt"
    plain.write_bytes(words)
    gz = tmp_path / "d.txt.gz"
    gz.write_bytes(gzip.compress(words))
    for p in (plain, gz):
        assert list(DictStream(str(p))) == [b"alpha", b"beta", b"gamma"]
    assert list(DictStream(str(gz), skip=1, limit=1)) == [b"beta"]
    assert list(DictStream(str(plain)).batches(2)) == [[b"alpha", b"beta"], [b"gamma"]]
    assert md5_file(str(plain)) == hashlib.md5(words).hexdigest()


def test_dict_stream_fileobj():
    buf = io.BufferedReader(io.BytesIO(b"one1234\ntwo5678\n"))
    assert list(DictStream(buf)) == [b"one1234", b"two5678"]


def test_dictstream_reiterates_caller_fileobj():
    """A caller-supplied fileobj survives iteration and can be re-read
    (ADVICE r1: DictStream used to close it after the first pass)."""
    import io
    from dwpa_tpu.gen.dicts import DictStream

    buf = io.BytesIO(b"alpha\nbeta\n\ngamma\n")
    ds = DictStream(buf)
    assert list(ds) == [b"alpha", b"beta", b"gamma"]
    assert list(ds) == [b"alpha", b"beta", b"gamma"]
    assert not buf.closed


def test_dictstream_sniffs_gzip_bytesio():
    import gzip, io
    from dwpa_tpu.gen.dicts import DictStream

    buf = io.BytesIO(gzip.compress(b"one\ntwo\n"))
    assert list(DictStream(buf)) == [b"one", b"two"]
    assert list(DictStream(buf)) == [b"one", b"two"]


# ---------------------------------------------------------------------------
# the bundled WPA ruleset (the bestWPA.rule asset equivalent)


def test_wpa_rule_asset_fully_parses():
    from dwpa_tpu.rules import WPA_RULE_PATH, parse_rules, wpa_rules

    with open(WPA_RULE_PATH) as f:
        lines = [ln for ln in f.read().splitlines()
                 if ln.strip() and not ln.lstrip().startswith("#")]
    rules = parse_rules(lines, on_error="raise")  # every line must parse
    assert len(rules) == len(lines) == len(wpa_rules())
    assert len(rules) >= 100  # a real ruleset, not a stub


def test_wpa_rules_expand_expected_shapes():
    from dwpa_tpu.rules import apply_rules, wpa_rules

    out = set(apply_rules(wpa_rules(), [b"password"]))
    for expect in (b"password", b"Password", b"PASSWORD", b"password1",
                   b"password123", b"password2024", b"p@ssword",
                   b"passw0rd", b"drowssap", b"passwordpassword"):
        assert expect in out, expect


def test_apply_rules_pooled_matches_serial():
    """workers>1 must yield the exact serial stream (order included) —
    resume skip-by-count depends on it."""
    from dwpa_tpu.rules import apply_rules, parse_rules

    rules = parse_rules([":", "c", "$1", "se3", "r", "] ]"])
    words = [b"poolword%04d" % i for i in range(500)]
    serial = list(apply_rules(rules, words))
    # force_pool: the few-cores guard must not silently serialize the
    # very path this test exists to pin.
    pooled = list(apply_rules(rules, iter(words), workers=3, force_pool=True))
    assert pooled == serial


def test_apply_rules_pool_guard_falls_back_serial(monkeypatch, caplog):
    """On a host without spare cores the pool is auto-disabled (with a
    warning) and the serial stream is produced instead — --rule-workers
    must never make a deployment slower."""
    import logging

    from dwpa_tpu.rules import apply_rules, parse_rules
    from dwpa_tpu.rules import engine as eng

    rules = parse_rules([":", "u", "$9"])
    words = [b"guardword%02d" % i for i in range(20)]
    monkeypatch.setattr(eng, "_usable_cpus", lambda: 2)
    monkeypatch.setattr(eng, "_POOL_GUARD_WARNED", set())

    def boom(*a, **k):  # the pool must not even be touched
        raise AssertionError("pool used despite guard")

    monkeypatch.setattr(eng, "_apply_rules_pooled", boom)
    with caplog.at_level(logging.WARNING, logger="dwpa_tpu.rules.engine"):
        out = list(apply_rules(rules, words, workers=8))
    assert out == list(apply_rules(rules, words))
    assert any("pool disabled" in r.message for r in caplog.records)
