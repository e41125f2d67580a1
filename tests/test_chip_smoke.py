"""chip_smoke.py's phases on the CPU at tiny sizes, and its refusals.

The phases are the same functions the chip run calls; here the scatter
path is the scan (the MXU updates are a TPU's), so ``require_chip_path``
is off.
"""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def clock():
    return chip_smoke._CompileClock()


def test_pca_phase_matches_golden(clock):
    out = chip_smoke.phase_pca(clock, n=96, v=1024)
    assert out["finish"] == "fused"
    assert out["parity"] <= chip_smoke.PARITY_BAR


def test_pca_phase_fails_when_auto_falls_back(clock, monkeypatch):
    """The phase reads which finish ran from the driver's own notes: an
    AUTO fall to dense eigh passes parity but must fail the phase."""
    from spark_examples_tpu.ops import fused

    def rank_deficient(*_, **__):
        raise fused.RankDeficientGramianError("planted rank-deficient panel")

    monkeypatch.setattr(fused, "fused_finish", rank_deficient)
    with pytest.warns(UserWarning, match="dense-eigh finish"):
        with pytest.raises(AssertionError, match="dense-eigh fallback ran"):
            chip_smoke.phase_pca(clock, n=96, v=1024)


def test_sparse_phase_scatters_exactly(clock):
    out = chip_smoke.phase_sparse(
        clock, require_chip_path=False, n=1024, v=2048
    )
    assert out["windows"] == {"scan": 1}


def test_sparse_phase_refuses_scan_where_an_mxu_update_is_required(clock):
    with pytest.raises(AssertionError, match="on an int8 MXU update"):
        chip_smoke.phase_sparse(
            clock, require_chip_path=True, n=1024, v=2048
        )


def test_mesh_phase_uses_every_device(clock):
    out = chip_smoke.phase_mesh(
        clock, require_chip_path=False, n=96, v=1024, sparse_n=1024,
        sparse_v=2048,
    )
    assert set(out) == {"mesh-dense", "mesh-sparse", "mesh-scatter"}
    assert out["mesh-scatter"]["windows"] == {"scan": 1}


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 2
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_refuses_alone_on_cpu(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
