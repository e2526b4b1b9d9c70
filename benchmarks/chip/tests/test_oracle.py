import numpy as np

import datagen
import oracle

CFG = {"dim": 3000, "data": {"generator": "synthetic", "structure_seed": 5,
                             "nnz_mean": 40, "nnz_std": 10,
                             "weight_low": 0.001, "weight_high": 1.0}}


def _setup(k=5):
    s = datagen.generate(CFG, 400, 1, part=0)
    q = datagen.generate(CFG, 30, 1, part=1)
    ref = oracle.Reference(oracle.csr64(*s, CFG["dim"]), k)
    return s, q, ref.run(*q, CFG["dim"])


def _dense(rows, dim):
    idx, val, nnz = rows
    out = np.zeros((idx.shape[0], dim))
    for i in range(idx.shape[0]):
        out[i, idx[i, :nnz[i]]] = val[i, :nnz[i]]
    return out


def test_reference_matches_brute_force():
    s, q, (scores, ids, table) = _setup()
    brute = _dense(q, CFG["dim"]) @ _dense(s, CFG["dim"]).T
    np.testing.assert_allclose(table, brute, rtol=1e-12, atol=1e-12)
    for i in range(brute.shape[0]):
        want = np.sort(brute[i])[::-1][:5]
        np.testing.assert_allclose(scores[i], want, rtol=1e-12)
        np.testing.assert_allclose(brute[i, ids[i]], want, rtol=1e-12)


def test_exact_answers_read_zero_and_faults_read_high():
    _, _, (scores, ids, table) = _setup()
    rows = list(range(len(ids)))
    exact = oracle.compare([ids[i] for i in rows], [scores[i] for i in rows], scores, table)
    assert exact == {"gap": 0.0, "bad_rows": 0}
    # float32 rounding of the scores reads about 1e-7
    f32 = oracle.compare([ids[i] for i in rows],
                         [scores[i].astype(np.float32) for i in rows], scores, table)
    assert 0 < f32["gap"] < 1e-6
    # a wrong neighbour at the top rank reads the distance to the right one
    wrong = ids.copy()
    wrong[3, 0] = ids[3, 4] + 1 if ids[3, 4] + 1 not in ids[3] else ids[3, 4] + 2
    out = oracle.compare(list(wrong), list(scores), scores, table)
    assert out["gap"] > 1e-3 and out["bad_rows"] == 0


def test_unanswered_repeated_and_out_of_range_answers_are_bad_rows():
    _, _, (scores, ids, table) = _setup()
    prog_ids = list(ids.copy())
    prog_scores = list(scores)
    prog_ids[0] = np.full(5, -1)
    prog_ids[1] = np.array([ids[1, 0]] * 5)
    prog_ids[2] = np.array([-1, *ids[2, 1:]])
    out = oracle.compare(prog_ids, prog_scores, scores, table)
    assert out["bad_rows"] == 3
    assert not oracle.verdict(out, {"gap": 1e-6, "bad_rows": 0})
