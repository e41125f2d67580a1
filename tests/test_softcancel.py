"""Soft-cancel run deadlines (utils/softcancel.py + scripts/tpu_run.sh).

A ``timeout``-style SIGKILL can land mid-dispatch and kill a run with
no checkpoint or telemetry flush. These tests pin the cooperative
replacement: the driver exits cleanly (code 75) at
a BLOCK BOUNDARY when the wrapper's deadline passes, and the wrapper
escalates to signals only after the grace period.
"""

import os
import subprocess
import sys
import time

import numpy as np
import pytest

from spark_examples_tpu.genomics.fixtures import (
    DEFAULT_VARIANT_SET_ID,
    synthetic_cohort,
)
from spark_examples_tpu.utils import softcancel

REFS = "17:41196311:41277499"


class TestSoftCancelCheck:
    def test_noop_without_env(self):
        softcancel.check("anywhere", environ={})

    def test_future_deadline_is_noop(self):
        env = {softcancel.SOFT_DEADLINE_ENV: str(time.time() + 3600)}
        softcancel.check("anywhere", environ=env)
        assert softcancel.remaining(environ=env) > 3500

    def test_past_deadline_raises_clean_exit_75(self, capsys):
        env = {softcancel.SOFT_DEADLINE_ENV: str(time.time() - 5)}
        with pytest.raises(SystemExit) as exc:
            softcancel.check("block boundary", environ=env)
        assert exc.value.code == softcancel.SOFT_CANCEL_EXIT == 75
        assert "block boundary" in capsys.readouterr().err

    def test_unparseable_deadline_is_loud(self):
        env = {softcancel.SOFT_DEADLINE_ENV: "tomorrow"}
        with pytest.raises(ValueError, match="unix timestamp"):
            softcancel.check("anywhere", environ=env)


class TestDriverBlockBoundary:
    def _driver(self, source):
        from spark_examples_tpu.models.pca import VariantsPcaDriver
        from spark_examples_tpu.utils.config import PcaConfig

        conf = PcaConfig(
            references=REFS,
            variant_set_ids=[DEFAULT_VARIANT_SET_ID],
            bases_per_partition=20_000,
        )
        return VariantsPcaDriver(conf, source)

    @pytest.fixture()
    def cohort(self, tmp_path):
        from spark_examples_tpu.genomics.sources import JsonlSource

        root = str(tmp_path / "cohort")
        synthetic_cohort(12, 60, seed=3).dump(root)
        return JsonlSource(root)

    def test_ingest_cancels_at_block_boundary(self, monkeypatch, cohort):
        drv = self._driver(cohort)
        monkeypatch.setenv(
            softcancel.SOFT_DEADLINE_ENV, str(time.time() - 1)
        )
        with pytest.raises(SystemExit) as exc:
            drv.get_similarity_matrix_csr(drv.get_csr_fused())
        assert exc.value.code == 75

    def test_run_completes_without_deadline(self, monkeypatch, cohort):
        monkeypatch.delenv(softcancel.SOFT_DEADLINE_ENV, raising=False)
        drv = self._driver(cohort)
        g = np.asarray(
            drv.get_similarity_matrix_csr(drv.get_csr_fused())
        )
        assert g.shape == (12, 12)


class TestRunWrapper:
    WRAPPER = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts",
        "tpu_run.sh",
    )

    def test_exports_absolute_deadline_and_passes_exit_code(self):
        proc = subprocess.run(
            [
                "bash",
                self.WRAPPER,
                "-d",
                "60",
                "--",
                sys.executable,
                "-c",
                "import os, time, sys;"
                "d = float(os.environ['SPARK_EXAMPLES_TPU_SOFT_DEADLINE']);"
                "sys.exit(0 if 50 < d - time.time() <= 60 else 3)",
            ],
            capture_output=True,
            timeout=30,
        )
        assert proc.returncode == 0, proc.stderr.decode()

    def test_soft_cancel_exit_code_passes_through(self):
        proc = subprocess.run(
            ["bash", self.WRAPPER, "-d", "60", "--", "bash", "-c", "exit 75"],
            capture_output=True,
            timeout=30,
        )
        assert proc.returncode == 75

    def test_escalates_to_sigterm_after_grace(self):
        t0 = time.monotonic()
        proc = subprocess.run(
            ["bash", self.WRAPPER, "-d", "0", "-g", "1", "--", "sleep", "30"],
            capture_output=True,
            timeout=30,
        )
        assert proc.returncode == 124
        assert time.monotonic() - t0 < 15
        assert b"SIGTERM" in proc.stderr
        # the pre-escalation liveness snapshot makes a wedge attributable
        assert b"liveness snapshot" in proc.stderr
