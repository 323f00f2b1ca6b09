"""Outer plugin discovery (reference:
``mythril/plugin/discovery.py`` entry-point loading ⚠unv, SURVEY §2 row
"Mythril plugin system (outer)").

Covers both channels: a plugin DIRECTORY of plain .py files (no install
needed) and installed-package entry points (faked via monkeypatched
``importlib.metadata``), plus per-plugin failure isolation.
"""

import textwrap

import pytest

import mythril_tpu  # noqa: F401
from mythril_tpu.analysis import ModuleLoader
from mythril_tpu.analysis.module import loader as module_loader
from mythril_tpu.plugin import (LaserPlugin, discover_entrypoints,
                                load_plugin_dir)


@pytest.fixture(autouse=True)
def _isolated_registry():
    """Discovery installs into the process-global detection registry;
    restore it so later tests (exact detector counts, fire_lasers) don't
    see the dummies."""
    saved = list(module_loader._REGISTRY)
    inst = ModuleLoader()
    saved_mods = list(inst._modules)
    yield
    module_loader._REGISTRY[:] = saved
    inst._modules[:] = saved_mods

PLUGIN_SRC = textwrap.dedent("""
    from mythril_tpu.analysis.module.base import DetectionModule, EntryPoint
    from mythril_tpu.plugin import LaserPlugin

    class ExternalDetector(DetectionModule):
        name = "ExternalDetector"
        swc_id = "000"
        description = "third-party detection module"

        def _execute(self, ctx):
            return []

    class ExternalHook(LaserPlugin):
        name = "external-hook"

    MYTHRIL_PLUGINS = [ExternalDetector, ExternalHook()]
""")


def test_plugin_dir_registers_modules_and_plugins(tmp_path):
    (tmp_path / "ext.py").write_text(PLUGIN_SRC)
    (tmp_path / "broken.py").write_text("raise RuntimeError('boom')\n")
    disc = load_plugin_dir(str(tmp_path))
    assert "ExternalDetector" in disc.detection_modules
    assert [p.name for p in disc.laser_plugins] == ["external-hook"]
    # a broken file is isolated, not fatal
    assert "broken.py" in disc.errors and "boom" in disc.errors["broken.py"]
    # the detection module is now live in the global registry
    mods = ModuleLoader().get_detection_modules(
        white_list=["ExternalDetector"])
    assert len(mods) == 1 and mods[0].name == "ExternalDetector"


def test_plugin_dir_without_manifest_scans_classes(tmp_path):
    (tmp_path / "bare.py").write_text(textwrap.dedent("""
        from mythril_tpu.plugin import LaserPlugin

        class BarePlugin(LaserPlugin):
            name = "bare"
    """))
    disc = load_plugin_dir(str(tmp_path))
    assert [p.name for p in disc.laser_plugins] == ["bare"]
    assert not disc.errors


def test_entrypoint_discovery(monkeypatch):
    class GoodPlugin(LaserPlugin):
        name = "from-entrypoint"

    class FakeEP:
        def __init__(self, name, obj=None, exc=None):
            self.name, self._obj, self._exc = name, obj, exc

        def load(self):
            if self._exc:
                raise self._exc
            return self._obj

    import importlib.metadata as metadata

    def fake_eps(group=None):
        assert group == "mythril_tpu.plugins"
        return [FakeEP("good", GoodPlugin),
                FakeEP("bad", exc=ImportError("missing dep")),
                FakeEP("junk", obj=42)]

    monkeypatch.setattr(metadata, "entry_points", fake_eps)
    disc = discover_entrypoints()
    assert [p.name for p in disc.laser_plugins] == ["from-entrypoint"]
    assert "bad" in disc.errors and "junk" in disc.errors


def test_cli_list_detectors_with_plugin_dir(tmp_path, capsys):
    from mythril_tpu.interfaces.cli import main

    (tmp_path / "ext2.py").write_text(PLUGIN_SRC.replace(
        "ExternalDetector", "ExternalDetector2"))
    rc = main(["list-detectors", "--plugin-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0 and "ExternalDetector2" in out
