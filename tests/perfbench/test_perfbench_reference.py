"""The plain Count reference against hand-worked words and against the
engine on the CPU backend, and its control: the float32 sum differs from
the exact one past 2^24, and put in the program's place through the
run's own comparison it comes out as not correct."""
import numpy as np
import pytest

from perfbench import run
from perfbench.datagen import segmentation
from perfbench.reference import bitmap_count


@pytest.fixture(scope="module")
def engine():
    from pilosa_tpu.executor import Executor
    from pilosa_tpu.testing import TestHolder

    with TestHolder() as holder:
        yield holder, Executor(holder)


SEG = {"datagen": "segmentation",
       "shape": {"index": "users", "slices": 3, "rows": 9,
                 "frames": ["behavior", "device", "geo"],
                 "and_depths": [1, 2, 3]}}


def _seg_reference(seed, slices=3):
    cfg = {**SEG, "shape": {**SEG["shape"], "slices": slices}}
    dense = np.stack([segmentation.gen_slice(cfg, seed, s)
                      for s in range(slices)], axis=1)
    return cfg, dense, bitmap_count.Reference(cfg, {"dense": dense})


def test_count_reference_by_hand():
    cfg, dense, ref = _seg_reference(5)
    a, b, c = (segmentation.bitmap(cfg, r) for r in (0, 4, 8))
    pc = lambda x: int(np.bitwise_count(x).sum())
    got = ref.answers([f"Count({a})", f"Count(Intersect({a}, {b}))",
                       f"Count(Union({a}, {b}))",
                       f"Count(Difference({a}, {b}))",
                       f"Count(Xor({a}, {b}))",
                       f"Count(Intersect({a}, Difference({b}, {c})))"])
    assert got == [pc(dense[0]), pc(dense[0] & dense[4]),
                   pc(dense[0] | dense[4]), pc(dense[0] & ~dense[4]),
                   pc(dense[0] ^ dense[4]),
                   pc(dense[0] & dense[4] & ~dense[8])]
    # Densities are 2^-depth: 50, 25 and 12.5 %.
    assert got[0] / (3 << 20) == pytest.approx(0.5, abs=0.01)


def test_count_reference_equals_the_engine(engine):
    holder, ex = engine
    cfg, dense, ref = _seg_reference(11)
    idx = holder.create_index("users")
    for f in cfg["shape"]["frames"]:
        idx.create_frame(f)
    for r in range(9):
        frame, rid, _ = segmentation.row_home(cfg, r)
        bits = np.unpackbits(dense[r].view(np.uint8), bitorder="little")
        cols = np.nonzero(bits)[0].astype(np.uint64)
        idx.frame(frame).import_bits(
            np.full(len(cols), rid, dtype=np.uint64), cols)
    pools = segmentation.pools(cfg)["row"]
    qs = [f"Count(Intersect({pools[i]}, Difference({pools[j]}, {pools[k]})))"
          for i, j, k in ((0, 1, 2), (3, 7, 5), (8, 0, 4))]
    qs += [f"Count(Xor({pools[2]}, {pools[6]}))",
           f"Count(Union({pools[1]}, {pools[5]}))"]
    assert [ex.execute("users", q)[0] for q in qs] == ref.answers(qs)
    holder.delete_index("users")


def test_count_control_differs_past_two_to_the_24():
    # 40 slices of a 50 % row: 21 M bits, past float32's exact integers.
    cfg, dense, ref = _seg_reference(7, slices=40)
    a, b = segmentation.bitmap(cfg, 0), segmentation.bitmap(cfg, 3)
    qs = [f"Count({a})", f"Count(Union({a}, {b}))", f"Count(Xor({a}, {b}))"]
    exact, control = ref.answers(qs), ref.answers(qs, control=True)
    assert all(x > 1 << 24 for x in exact)
    assert sum(x != y for x, y in zip(exact, control)) >= 2
    # Below 2^24 the control is exact, so it has to be run at size.
    small = [f"Count(Intersect({segmentation.bitmap(cfg, 2)}, "
             f"{segmentation.bitmap(cfg, 5)}))"]
    assert ref.answers(small) == ref.answers(small, control=True)


@pytest.mark.parametrize("seed", [2_147_483_801, 2_147_483_802, 17])
def test_the_control_in_the_programs_place_is_not_correct(tmp_path, seed):
    """What ``--control`` does on the chip, at a size a test can hold:
    40 slices, so that the counts of the dense rows pass 2^24. The
    window's answers are the exact ones (a sound program); the control's
    stand in for them through ``compare`` and ``verdict``."""
    cfg, dense, ref = _seg_reference(seed, slices=40)
    rows = segmentation.pools(cfg)["row"]
    qs = [f"Count({op}({rows[i]}, {rows[j]}))"
          for op in ("Union", "Xor", "Difference", "Intersect")
          for i, j in ((0, 3), (3, 6), (0, 6), (1, 4))]
    qs += [f"Count(Intersect({rows[0]}, Difference({rows[3]}, {rows[k]})))"
           for k in (1, 2, 5, 8)]
    log = [{"ok": True, "status": 200, "pql": q, "result": a}
           for q, a in zip(qs, ref.answers(qs))]
    assert max(r["result"] for r in log) > 1 << 24
    picked, wrong, failed, control = run.compare(
        ref, log, str(tmp_path), None, seed, control=True)
    assert run.verdict(len(picked), wrong, failed) is True
    assert control >= 3
    assert run.verdict(len(picked), control, failed) is False
