"""The plain Tanimoto TopN reference by hand and against the engine on
the CPU backend at a small size; its bfloat16 control, put in the
program's place through the run's own comparison, comes out as not
correct; and a run of the TopN cell whose timed path is broken
underneath (a pair dropped, a count off by one, the gate made ``>=``)
reads ``correct: false``."""
import argparse
import json
import os

import numpy as np
import pytest

from perfbench import run as pbrun
from perfbench.datagen import chem
from perfbench.lib import loadgen, pql, topn_bytes_model
from perfbench.reference import topn_tanimoto

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "chem500k-tanimoto-c1"
TOPN = ('TopN(Bitmap(frame="fingerprint", rowID={p}), frame="fingerprint", '
        'n={n}, tanimotoThreshold={t})')


def _config(molecules):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "chem-500k.json")) as f:
        config = json.load(f)
    config["shape"].update(molecules=molecules, cache_size=molecules)
    return config


def _data(config, seed):
    chunks = [c for _, c in chem.fingerprints(config, seed)]
    bits = np.concatenate(chunks)
    return bits, {"packed": chem.pack_rows(bits),
                  "counts": bits.sum(axis=1).astype(np.int64)}


def _pairs(ids_counts):
    return [{"id": i, "count": c} for i, c in ids_counts]


def test_topn_reference_by_hand():
    bits = np.zeros((7, 4096), dtype=bool)
    bits[0, :10] = True                    # the probe: 10 bits
    bits[1, :10] = True                    # a copy: 10 / 10
    bits[2, :7] = True                     # 7 / 10 = 70 %: on T=70, dropped
    bits[3, :8] = True                     # 8 / 10
    bits[4, 2:10] = True                   # 8 / 10 as well: a tie, id decides
    bits[5, :5] = True
    bits[5, 100:105] = True                # 5 / 15: on 33.3 %
    bits[6, 200:260] = True                # disjoint
    cfg = _config(7)
    ref = topn_tanimoto.Reference(cfg, {
        "packed": chem.pack_rows(bits), "counts": bits.sum(axis=1)})
    ask = lambda n, t: ref.answers([TOPN.format(p=0, n=n, t=t)])[0]
    assert ask(50, 70) == _pairs([(0, 10), (1, 10), (3, 8), (4, 8)])
    assert ask(50, 69) == _pairs([(0, 10), (1, 10), (3, 8), (4, 8), (2, 7)])
    assert ask(3, 70) == _pairs([(0, 10), (1, 10), (3, 8)])     # cut at n
    assert ask(50, 33) == _pairs([(0, 10), (1, 10), (3, 8), (4, 8), (2, 7),
                                  (5, 5)])                # 500 > 33 * 15
    assert ask(50, 34) == ask(50, 69)                     # 500 < 34 * 15
    assert ask(50, 100) == []                             # nothing beats 100
    assert ask(50, 99) == _pairs([(0, 10), (1, 10)])
    # The explanation of a difference says that row 2 lies on T=70.
    why = ref.explain(TOPN.format(p=0, n=50, t=70), ask(50, 69), ask(50, 70))
    assert [(r["id"], r["on_threshold"]) for r in why["differing_rows"]] \
        == [(2, True)]
    # The harness's own parser reads the query the mix sends.
    call = pql.parse(TOPN.format(p=3, n=50, t=90))
    assert call.args["tanimotoThreshold"] == 90 \
        and call.children[0].args["rowID"] == 3


def test_topn_request_bytes_by_hand():
    """One scan of the fragment and a recount of the answer's rows,
    from the shape and the answer alone."""
    scan = 500_000 * (512 + 4) + 512
    assert topn_bytes_model.scan_bytes(500_000, 4096) == scan == 258_000_512
    assert topn_bytes_model.request_bytes(0, 500_000, 4096) == scan
    assert topn_bytes_model.request_bytes(50, 500_000, 4096) \
        == scan + 50 * 512
    assert topn_bytes_model.row_bytes(4097) == 513


def test_staging_sends_two_distinct_re_queries_a_bucket():
    queries = chem.stage_queries(_config(2000))
    assert len(set(queries)) == len(queries) == 1 + 2 * 7
    sizes = [q[q.index("["):q.index("]")].count(",") + 1
             for q in queries[1:]]
    assert sizes == [1, 1, 2, 2, 4, 4, 8, 8, 16, 16, 32, 32, 64, 64]


@pytest.fixture(scope="module")
def engine():
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.storage.index import FrameOptions
    from pilosa_tpu.testing import TestHolder

    with TestHolder() as holder:
        yield holder, Executor(holder), FrameOptions


@pytest.mark.parametrize("t, n", [(70, 50), (50, 10), (90, 5)])
@pytest.mark.parametrize("seed", [2_147_483_901, 2_147_483_902, 19])
def test_topn_reference_equals_the_engine(engine, seed, t, n):
    holder, ex, FrameOptions = engine
    cfg = _config(1500)
    bits, data = _data(cfg, seed)
    ref = topn_tanimoto.Reference(cfg, data)
    idx = holder.create_index("mol")
    frame = idx.create_frame("fingerprint", FrameOptions(cache_size=1500))
    rows, cols = np.nonzero(bits)
    frame.import_bits(rows.astype(np.uint64), cols.astype(np.uint64))
    probes = np.random.default_rng(seed).choice(1500, 40, replace=False)
    qs = [TOPN.format(p=int(p), n=n, t=t) for p in probes]
    want = ref.answers(qs)
    got = [_pairs(ex.execute("mol", q)[0]) for q in qs]
    holder.delete_index("mol")
    assert got == want
    # The data does what the configuration says of it: answers hold
    # more than the probe, and some are cut at n.
    assert max(len(a) for a in want) > 1
    assert n > 10 or any(len(a) == n for a in want)


@pytest.mark.parametrize("seed", [2_147_483_911, 2_147_483_912, 23])
def test_the_bfloat16_control_in_the_programs_place_is_not_correct(
        tmp_path, seed):
    """What ``--control`` does on the chip, at a size a test can hold.
    The window's answers are the reference's own (a sound program); the
    control's stand in for them through ``compare`` and ``verdict``."""
    cfg = _config(3000)
    _, data = _data(cfg, seed)
    ref = topn_tanimoto.Reference(cfg, data)
    probes = np.random.default_rng(seed).choice(3000, 120, replace=False)
    qs = [TOPN.format(p=int(p), n=50, t=t)
          for p, t in zip(probes, [70, 70, 70, 70, 70, 50, 50, 90] * 15)]
    log = [{"ok": True, "status": 200, "pql": q, "result": a}
           for q, a in zip(qs, ref.answers(qs))]
    picked, wrong, failed, control = pbrun.compare(
        ref, log, str(tmp_path), None, seed, control=True)
    assert pbrun.verdict(len(picked), wrong, failed) is True
    assert control >= 3
    assert pbrun.verdict(len(picked), control, failed) is False


# ------------------------------------------- a broken timed path

def _run(tmp_path, monkeypatch, tamper=None):
    decode = loadgen.decode

    def tampered(log):
        decode(log)
        if tamper is not None:
            tamper(log)
        return log

    monkeypatch.setattr(loadgen, "decode", tampered)
    args = argparse.Namespace(workload=CELL, seed=SEED, seconds=1.0, trace=0,
                              rehearse=True, control=False)
    data = tmp_path / "data"
    data.mkdir()
    return pbrun.run(args, str(tmp_path), str(data))


SEED = 2_147_483_777


def _longest(log):
    return max(log, key=lambda r: len(r["result"]))


def _a_pair_dropped(log):
    _longest(log)["result"].pop(1)


def _a_count_off_by_one(log):
    _longest(log)["result"][-1]["count"] += 1


def _the_gate_made_ge(log):
    """Every answer as a program whose gate reads ``>=`` would give it:
    the rows that lie exactly on the threshold are kept as well."""
    rehearsal = json.load(open(os.path.join(
        ROOT, "perfbench", "rehearsal", "chem-500k.json")))
    cfg = _config(rehearsal["shape"]["molecules"])
    _, data = _data(cfg, SEED)
    packed, counts = data["packed"], data["counts"]
    for r in log:
        call = pql.parse(r["pql"])
        p, t, n = (call.children[0].args["rowID"],
                   call.args["tanimotoThreshold"], call.args["n"])
        inter = np.bitwise_count(packed & packed[p]).sum(axis=1,
                                                         dtype=np.int64)
        keep = (inter > 0) & (100 * inter >= t * (counts + counts[p] - inter))
        ids = np.nonzero(keep)[0]
        order = np.lexsort((ids, -inter[ids]))[:n]
        r["result"] = _pairs((int(i), int(inter[i])) for i in ids[order])


def test_a_sound_topn_run_is_correct(tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch)
    assert out["correct"] is True and out["failed"] == 0
    assert out["compared"]["mismatched"] == [0, 0]
    assert out["compared"]["compared"][0] == out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and out["metrics"] == {}


@pytest.mark.parametrize("tamper", [_a_pair_dropped, _a_count_off_by_one,
                                    _the_gate_made_ge],
                         ids=lambda t: t.__name__)
def test_a_broken_topn_path_is_not_correct(tmp_path, monkeypatch, capsys,
                                           tamper):
    out = _run(tmp_path, monkeypatch, tamper)
    assert out["correct"] is False
    mismatched = out["compared"]["mismatched"][0]
    assert out["compared"]["failed"][0] == 0 and out["failed"] == mismatched
    if tamper is _the_gate_made_ge:
        assert 1 <= mismatched < out["attempted"]
    else:
        assert mismatched == 1
    report = json.load(open(tmp_path / "mismatch.json"))
    assert len(report["mismatched"]) == min(mismatched, 50)
    if tamper is _the_gate_made_ge:
        # Every row the broken gate lets in lies exactly on its query's
        # threshold (at the cut it pushes rows of the answer out).
        extra = [row for m in report["mismatched"]
                 for row in m["differing_rows"] if row["want"] is None]
        assert extra and all(row["on_threshold"] for row in extra)
    assert '"mismatch_report"' in capsys.readouterr().out
