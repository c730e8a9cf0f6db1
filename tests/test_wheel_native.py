"""Wheel-bundled native plane: loader preference + setup.py contract.

The full proof is CI's installed-wheel smoke (scripts/wheel_smoke.py in
a clean venv — ci.yml `wheel` job); these are the fast in-tree contract
pieces: the ctypes-extension filename mapping that puts the .so INSIDE
the package, and the loader preferring a bundled library over the
source-tree one so an installed user never silently downgrades.
"""

import os


class TestLoaderPreference:
    def test_bundled_library_wins(self, monkeypatch, tmp_path):
        fake = tmp_path / "librelayrl_native.so"
        fake.write_bytes(b"")
        import relayrl_tpu._native as native_pkg

        monkeypatch.setattr(native_pkg, "bundled_library_path",
                            lambda: str(fake))
        assert native_pkg.find_library() == str(fake)

    def test_source_tree_fallback(self, monkeypatch):
        import relayrl_tpu._native as native_pkg

        monkeypatch.setattr(native_pkg, "bundled_library_path", lambda: None)
        found = native_pkg.find_library()
        # In this checkout the make-built lib exists; wherever it is, it
        # must NOT claim to be the bundled one.
        if found is not None:
            assert os.sep + "_native" + os.sep not in found

    def test_bundled_path_helper_is_honest(self):
        from relayrl_tpu._native import bundled_library_path

        p = bundled_library_path()
        # Source checkout: no .so inside the package dir (wheel builds
        # put it there); if present it must exist.
        assert p is None or os.path.isfile(p)


class TestSetupContract:
    def _mod(self):
        import importlib.util
        import sys

        spec = importlib.util.spec_from_file_location(
            "relayrl_setup", os.path.join(os.path.dirname(__file__),
                                          os.pardir, "setup.py"))
        mod = importlib.util.module_from_spec(spec)
        # setup() runs on import; neuter it
        import setuptools

        orig = setuptools.setup
        setuptools.setup = lambda **kw: None
        try:
            sys.modules["relayrl_setup"] = mod
            spec.loader.exec_module(mod)
        finally:
            setuptools.setup = orig
            sys.modules.pop("relayrl_setup", None)
        return mod

    def test_ext_filename_has_no_python_abi_suffix(self):
        mod = self._mod()
        builder = mod.build_ctypes_ext.__new__(mod.build_ctypes_ext)
        got = builder.get_ext_filename("relayrl_tpu._native.relayrl_native")
        assert got == os.path.join("relayrl_tpu", "_native",
                                   "librelayrl_native.so")

    def test_wheel_tag_is_py3_none(self):
        # the .so is ctypes — the wheel must not claim a CPython ABI
        mod = self._mod()
        src = open(os.path.join(os.path.dirname(__file__), os.pardir,
                                "setup.py")).read()
        assert '"py3", "none", plat' in src

    def test_ext_sources_exist_and_cover_native(self):
        mod = self._mod()
        repo = os.path.join(os.path.dirname(__file__), os.pardir)
        src = open(os.path.join(repo, "setup.py")).read()
        for cc in ("transport.cc", "codec.cc", "grpc_server.cc"):
            assert cc in src, f"setup.py must compile native/{cc}"
            assert os.path.isfile(os.path.join(repo, "native", cc))
