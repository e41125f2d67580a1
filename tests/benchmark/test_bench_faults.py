"""The comparison catches a broken timed path: each fault the cells can
have is planted under run.py (chip check stubbed, CPU, tiny size) and the
run has to come out not correct. The exchange between chips has no fault
here: every cell runs on one chip."""

import importlib

import numpy as np
import pytest

import bench_helpers as bh
from benchmark import run


def _every_third_step_unchanged(fn):
    calls = {"n": 0}

    def step(g, *args, **kwargs):
        calls["n"] += 1
        if calls["n"] % 3 == 1:
            return g
        return fn(g, *args, **kwargs)

    return step


def _plant(monkeypatch, fault):
    from spark_examples_tpu.arrays import blocks
    from spark_examples_tpu.models.pca import VariantsPcaDriver

    # The ops package re-exports functions under the modules' names.
    gramian = importlib.import_module("spark_examples_tpu.ops.gramian")
    sparse = importlib.import_module("spark_examples_tpu.ops.sparse")

    if fault == "state_unchanged":
        monkeypatch.setattr(gramian, "gramian_accumulate_packed",
                            _every_third_step_unchanged(
                                gramian.gramian_accumulate_packed))
        monkeypatch.setattr(sparse, "sparse_gramian_accumulate",
                            _every_third_step_unchanged(
                                sparse.sparse_gramian_accumulate))
    elif fault == "half_batch":
        windows = blocks.csr_windows

        def half(csr_iter, block_variants=blocks.DEFAULT_BLOCK_VARIANTS):
            for idx, lens in windows(csr_iter, block_variants):
                keep = max(lens.size // 2, 1)
                yield idx[:int(lens[:keep].sum())], lens[:keep]

        monkeypatch.setattr(blocks, "csr_windows", half)
    elif fault == "answer_altered":
        emit = VariantsPcaDriver._emit_tuples

        def altered(self, coords):
            coords = np.array(coords, dtype=np.float64)
            coords[0, 0] += 1e-3
            return emit(self, coords)

        monkeypatch.setattr(VariantsPcaDriver, "_emit_tuples", altered)
    else:
        raise AssertionError(fault)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", ["kgp3_chr20.auto",
                                      "nygc_chr20_rare.sparse",
                                      "nygc_chr20_rare_full.auto"])
def test_planted_fault_is_not_correct(tmp_path, monkeypatch, capsys,
                                      workload, fault):
    bh.stub_chip(monkeypatch)
    root = bh.tiny_root(tmp_path, sites=20000)
    _plant(monkeypatch, fault)
    code = run.main(["--workload", workload, "--seed", "2147483649",
                     "--seconds", "0.2", "--trace", "0"], root=root)
    out, err = capsys.readouterr()
    assert code == 0
    line = bh.last_json_line(out)
    assert line["correct"] is False, line["checks"]
    assert "check coord_err" in err.strip().splitlines()[-2]
