"""`repro.settings`: the one registry for every REPRO_* environment knob —
typed getters, env-wins semantics, and the docs table that cannot drift.
"""
import pathlib

import pytest

from repro import settings

REPO = pathlib.Path(__file__).resolve().parent.parent


class TestRegistry:
    def test_every_knob_is_documented(self):
        for name, knob in settings.KNOBS.items():
            assert name == knob.name
            assert name.startswith("REPRO_")
            assert knob.effect, f"{name} has no effect description"

    def test_unknown_knob_is_a_keyerror(self):
        with pytest.raises(KeyError, match="unknown settings knob"):
            settings.get_raw("REPRO_NO_SUCH_KNOB")
        with pytest.raises(KeyError, match="unknown settings knob"):
            settings.get_bool("TYPO")

    def test_describe_lists_all_knobs(self):
        desc = settings.describe()
        assert {row["name"] for row in desc} == set(settings.KNOBS)


class TestGetters:
    def test_env_wins_over_default(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
        assert settings.get_str("REPRO_KERNEL_BACKEND") == "reference"
        monkeypatch.delenv("REPRO_KERNEL_BACKEND")
        assert settings.get_str("REPRO_KERNEL_BACKEND") == "auto"

    def test_read_at_call_time_not_import_time(self, monkeypatch):
        monkeypatch.setenv("REPRO_MCMC_FUSED", "0")
        assert settings.get_bool("REPRO_MCMC_FUSED") is False
        monkeypatch.setenv("REPRO_MCMC_FUSED", "1")
        assert settings.get_bool("REPRO_MCMC_FUSED") is True

    @pytest.mark.parametrize("raw", ["0", "false", "off", "FALSE", "Off"])
    def test_bool_false_spellings(self, monkeypatch, raw):
        monkeypatch.setenv("REPRO_ENUM_PLAN_CACHE", raw)
        assert settings.get_bool("REPRO_ENUM_PLAN_CACHE") is False

    @pytest.mark.parametrize("raw", ["1", "true", "on", "yes", "anything"])
    def test_bool_true_spellings(self, monkeypatch, raw):
        monkeypatch.setenv("REPRO_ENUM_PLAN_CACHE", raw)
        assert settings.get_bool("REPRO_ENUM_PLAN_CACHE") is True

    def test_int_and_float(self, monkeypatch):
        monkeypatch.setenv("REPRO_ENUM_PLAN_CACHE_SIZE", "7")
        assert settings.get_int("REPRO_ENUM_PLAN_CACHE_SIZE") == 7
        monkeypatch.setenv("REPRO_BENCH_TOLERANCE", "0.5")
        assert settings.get_float("REPRO_BENCH_TOLERANCE") == 0.5

    def test_optional_float_unset_is_none(self, monkeypatch):
        monkeypatch.delenv("REPRO_SERVE_DEADLINE_MS", raising=False)
        assert settings.get_optional_float("REPRO_SERVE_DEADLINE_MS") is None
        monkeypatch.setenv("REPRO_SERVE_DEADLINE_MS", "250")
        assert settings.get_optional_float("REPRO_SERVE_DEADLINE_MS") == 250.0

    def test_is_set(self, monkeypatch):
        monkeypatch.delenv("REPRO_SERVE_DEADLINE_MS", raising=False)
        assert not settings.is_set("REPRO_SERVE_DEADLINE_MS")
        monkeypatch.setenv("REPRO_SERVE_DEADLINE_MS", "250")
        assert settings.is_set("REPRO_SERVE_DEADLINE_MS")


class TestDocsTable:
    def test_backends_md_table_matches_registry(self):
        page = (REPO / "docs" / "backends.md").read_text()
        assert settings.documented_env_table(page) == settings.render_env_table()

    def test_render_mentions_every_knob(self):
        table = settings.render_env_table()
        for name in settings.KNOBS:
            assert name in table

    def test_extractor_requires_markers(self):
        with pytest.raises(ValueError, match="settings table markers"):
            settings.documented_env_table("no markers here")


class TestCallSitesUseSettings:
    """The knob consolidation is real: the modules that used to read
    os.environ directly now resolve through `repro.settings`."""

    def test_kernel_backend_knob(self, monkeypatch):
        from repro.kernels import ops

        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
        assert ops.resolve_backend() == "reference"

    def test_serve_deadline_knob(self, monkeypatch):
        from repro.serve import InferenceServer

        monkeypatch.setenv("REPRO_SERVE_DEADLINE_MS", "123.0")
        server = InferenceServer({})
        try:
            assert server.default_deadline_ms == 123.0
        finally:
            server._httpd.server_close()
