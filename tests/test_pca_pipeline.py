"""End-to-end PCoA pipeline tests over the hermetic fixture (SURVEY.md §7's
minimum end-to-end slice, run on the CPU mesh)."""

import numpy as np
import pytest

from spark_examples_tpu.genomics.fixtures import (
    DEFAULT_VARIANT_SET_ID,
    synthetic_cohort,
)
from spark_examples_tpu.models.pca import VariantsPcaDriver
from spark_examples_tpu.ops import mllib_principal_components_reference
from spark_examples_tpu.utils.config import PcaConfig


def make_driver(tmp_path=None, n=40, v=300, **conf_kw):
    conf = PcaConfig(
        variant_set_ids=[DEFAULT_VARIANT_SET_ID],
        output_path=str(tmp_path / "out") if tmp_path else None,
        block_variants=64,
        **conf_kw,
    )
    source = synthetic_cohort(n, v, references=conf.references)
    return VariantsPcaDriver(conf, source), source


def reference_pipeline_numpy(source, conf):
    """Straight-line numpy re-implementation of the whole reference pipeline
    (ingest → scalar-loop Gramian → MLlib PCs) as the e2e golden."""
    from spark_examples_tpu.genomics.callsets import CallsetIndex
    from spark_examples_tpu.genomics.datasets import af_filter, calls_stream

    index = CallsetIndex.from_source(source, conf.variant_set_ids)
    shards = conf.shards(all_references=conf.all_references)
    variants = [
        v
        for s in shards
        for v in source.stream_variants(conf.variant_set_ids[0], s)
    ]
    variants = list(af_filter(variants, conf.min_allele_frequency))
    n = index.size
    g = np.zeros((n, n), dtype=np.int64)
    for calls in calls_stream([variants], index.indexes):
        for c1 in calls:
            for c2 in calls:
                g[c1, c2] += 1
    coords, _ = mllib_principal_components_reference(g, 2)
    return index, coords


class TestEndToEnd:
    def test_pipeline_matches_reference_semantics(self, tmp_path):
        driver, source = make_driver(tmp_path)
        result = driver.run()

        golden_source = synthetic_cohort(40, 300)
        index, golden = reference_pipeline_numpy(golden_source, driver.conf)

        got = np.array([[pc1, pc2] for _, pc1, pc2 in result])
        np.testing.assert_allclose(got, golden, atol=1e-4)

        # Output file format parity: name\tpc1\tpc2\tdataset, sorted by name.
        lines = (tmp_path / "out-pca.tsv").read_text().strip().split("\n")
        assert len(lines) == 40
        names = [l.split("\t")[0] for l in lines]
        assert names == sorted(names)
        assert all(len(l.split("\t")) == 4 for l in lines)

    def test_population_structure_separates(self, tmp_path):
        """PC1 should separate the two synthetic populations — signal, not
        just numerics."""
        conf = PcaConfig(variant_set_ids=[DEFAULT_VARIANT_SET_ID])
        source = synthetic_cohort(30, 400, population_structure=2, seed=3)
        driver = VariantsPcaDriver(conf, source)
        result = driver.run()
        import numpy as np

        rng = np.random.default_rng(3)
        groups = rng.integers(0, 2, size=30)  # same draw as the fixture
        pc1 = np.array([r[1] for r in result])
        means = [pc1[groups == g].mean() for g in (0, 1)]
        spread = abs(means[0] - means[1])
        within = max(pc1[groups == g].std() for g in (0, 1))
        assert spread > within  # clear separation

    def test_af_filter_reduces_variants(self):
        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID],
            min_allele_frequency=0.4,
        )
        source = synthetic_cohort(20, 200)
        driver = VariantsPcaDriver(conf, source)
        calls = list(driver.get_calls([driver.filter_dataset(d) for d in driver.get_data()]))
        source2 = synthetic_cohort(20, 200)
        conf2 = PcaConfig(variant_set_ids=[DEFAULT_VARIANT_SET_ID])
        driver2 = VariantsPcaDriver(conf2, source2)
        calls2 = list(driver2.get_calls([driver2.filter_dataset(d) for d in driver2.get_data()]))
        assert 0 < len(calls) < len(calls2)

    def test_dropped_contigs_excluded(self):
        conf = PcaConfig(variant_set_ids=[DEFAULT_VARIANT_SET_ID])
        source = synthetic_cohort(10, 100, dropped_contig_every=4)
        driver = VariantsPcaDriver(conf, source)
        calls = list(driver.get_calls(driver.get_data()))
        # 25 of 100 variants are on chrX_alt and must be dropped.
        assert len(calls) <= 75

    def test_multi_dataset_merge_pipeline(self):
        """Two variantsets: join semantics through the full driver."""
        from spark_examples_tpu.genomics.sources import FixtureSource

        a = synthetic_cohort(8, 60, variant_set_id="setA", seed=1)
        b = synthetic_cohort(8, 60, variant_set_id="setB", seed=1)
        # Same seed → same positions/alleles → full overlap; distinct callsets.
        merged = FixtureSource(
            variants=a._variants + b._variants,
            callsets=a._callsets + b._callsets,
        )
        conf = PcaConfig(variant_set_ids=["setA", "setB"])
        driver = VariantsPcaDriver(conf, merged)
        result = driver.run()
        assert len(result) == 16
        # Dataset label is the callsetId prefix before "-".
        assert {r[0].split("-")[0] for r in result} == {"setA", "setB"}

    def _degenerate_merge_driver(self, mode):
        """A same-seed two-dataset merge: duplicated sample rows make
        the centered Gramian exactly rank-deficient (rank ≤ 7 for 16
        samples, under the 10-wide fused panel) — the cohort shape the
        fused finish's explicit panel-rank check must catch, whatever
        the runtime's arithmetic does with CholeskyQR afterwards."""
        from spark_examples_tpu.genomics.sources import FixtureSource

        a = synthetic_cohort(8, 60, variant_set_id="setA", seed=1)
        b = synthetic_cohort(8, 60, variant_set_id="setB", seed=1)
        merged = FixtureSource(
            variants=a._variants + b._variants,
            callsets=a._callsets + b._callsets,
        )
        conf = PcaConfig(
            variant_set_ids=["setA", "setB"], pca_mode=mode
        )
        return VariantsPcaDriver(conf, merged)

    def test_degenerate_cohort_auto_falls_back_to_dense_eigh(self):
        """AUTO selection must not die on a rank-deficient centered
        Gramian: the fused finish's rank check warns and falls back to
        dense eigh (exact on rank-deficient spectra), finishing with
        finite coordinates."""
        driver = self._degenerate_merge_driver("auto")
        with pytest.warns(UserWarning, match="dense-eigh finish"):
            result = driver.run()
        coords = np.array([r[1:] for r in result])
        assert np.isfinite(coords).all()
        assert len(result) == 16

    def test_degenerate_cohort_forced_fused_still_raises(self):
        """--pca-mode fused asked for exactly that program: the
        rank-deficient panel stays a hard error there."""
        from spark_examples_tpu.ops.fused import RankDeficientGramianError

        driver = self._degenerate_merge_driver("fused")
        with pytest.raises(RankDeficientGramianError, match="rank-deficient"):
            driver.run()


class TestCli:
    def test_cli_pca_fixture(self, capsys, tmp_path):
        from spark_examples_tpu.cli.main import main

        rc = main(
            [
                "pca",
                "--fixture-samples",
                "12",
                "--fixture-variants",
                "80",
                "--output-path",
                str(tmp_path / "cli"),
            ]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "Matrix size: 12" in out
        assert "Non zero rows in matrix:" in out
        assert (tmp_path / "cli-pca.tsv").exists()

    def test_cli_generate_then_ingest(self, capsys, tmp_path):
        from spark_examples_tpu.cli.main import main

        rc = main(
            [
                "generate-fixture",
                "--fixture-samples",
                "9",
                "--fixture-variants",
                "40",
                "--out",
                str(tmp_path / "cohort"),
            ]
        )
        assert rc == 0
        rc = main(["pca", "--input-path", str(tmp_path / "cohort")])
        assert rc == 0
        out = capsys.readouterr().out
        assert "Matrix size: 9" in out


def test_stream_similarity_matches_dense():
    import numpy as np

    from spark_examples_tpu.genomics.fixtures import (
        DEFAULT_VARIANT_SET_ID,
        synthetic_cohort,
    )
    from spark_examples_tpu.models.pca import VariantsPcaDriver
    from spark_examples_tpu.utils.config import PcaConfig

    conf = PcaConfig(variant_set_ids=[DEFAULT_VARIANT_SET_ID], block_variants=32)
    driver = VariantsPcaDriver(conf, synthetic_cohort(12, 90))
    calls = list(driver.get_calls(driver.get_data()))
    dense = np.asarray(driver.get_similarity_matrix(iter(calls)))
    stream = np.asarray(driver.get_similarity_matrix_stream(iter(calls)))
    np.testing.assert_array_equal(dense, stream)


class TestFusedPcaMode:
    """--pca-mode routing and fused-vs-stream coordinate parity
    (round-5: the fused finish is the shipped default, VariantsPca.scala's
    main running its fast dense path, VariantsPca.scala:38-50)."""

    def _structured_driver(self, mode, tmp_path=None, **kw):
        conf = PcaConfig(
            variant_set_ids=[DEFAULT_VARIANT_SET_ID],
            block_variants=64,
            pca_mode=mode,
            **kw,
        )
        # population_structure=2 gives a clean top-2 eigenbasis so the
        # 1e-4 fused/stream parity bar is well-defined.
        source = synthetic_cohort(
            48, 400, population_structure=2, seed=3, references=conf.references
        )
        return VariantsPcaDriver(conf, source)

    def test_fused_matches_stream_coordinates(self):
        fused = self._structured_driver("fused").run()
        stream = self._structured_driver("stream").run()
        a = np.array([[p1, p2] for _, p1, p2 in fused])
        b = np.array([[p1, p2] for _, p1, p2 in stream])
        assert np.abs(a - b).max() <= 1e-4
        assert [r[0] for r in fused] == [r[0] for r in stream]

    def test_auto_routes_fused_at_small_n_and_stream_above_limit(self):
        d = self._structured_driver("auto")
        g = np.eye(4, dtype=np.float32)
        assert d._pca_fused_eligible(g)
        d_big = self._structured_driver("auto", dense_eigh_limit=8)
        assert not d_big._pca_fused_eligible(g)  # N=48 > 8
        d_stream = self._structured_driver("stream")
        assert not d_stream._pca_fused_eligible(g)
        d_precise = self._structured_driver("auto", precise=True)
        assert not d_precise._pca_fused_eligible(g)

    def test_forced_fused_rejects_incompatible_configs_before_ingest(self):
        with pytest.raises(ValueError, match="pca-mode fused"):
            self._structured_driver("fused", precise=True)

    def test_fused_nonzero_rows_print_matches_stream(self, capsys):
        self._structured_driver("fused").run()
        out_fused = capsys.readouterr().out
        self._structured_driver("stream").run()
        out_stream = capsys.readouterr().out
        line = [
            l for l in out_fused.splitlines() if "Non zero rows" in l
        ]
        assert line and line == [
            l for l in out_stream.splitlines() if "Non zero rows" in l
        ]


def test_stream_similarity_host_memory_fence():
    """The stream alternate now runs through the sparse device engine:
    the bound is the streaming-sparse per-device footprint (the f32 G
    tiles), NOT the historical 16·N² host peak (round-4 verdict) —
    past it the refusal is still loud, never a silent OOM. The full
    bound matrix lives in tests/test_sparse_gramian.py."""
    conf = PcaConfig(variant_set_ids=[DEFAULT_VARIANT_SET_ID], block_variants=32)
    driver = VariantsPcaDriver(conf, synthetic_cohort(12, 90))
    calls = list(driver.get_calls(driver.get_data()))
    with pytest.raises(ValueError, match="GiB"):
        driver.get_similarity_matrix_stream(
            iter(calls), max_device_bytes=4 * 12 * 12 - 1
        )
    # At exactly the f32-G per-device footprint it runs — a budget 4x
    # under the old int64-G + f32-copy + jax-buffer peak.
    out = driver.get_similarity_matrix_stream(
        iter(calls), max_device_bytes=4 * 12 * 12
    )
    assert out.shape == (12, 12)
