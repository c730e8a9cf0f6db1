"""Container package for the bundled native library, and its finder.

Wheel builds place ``librelayrl_native.so`` here (see setup.py); source
checkouts use ``native/librelayrl_native.so`` built by ``make -C
native``. :func:`find_library` checks both."""

import os

_LIB_NAMES = ("librelayrl_native.so",)


def bundled_library_path() -> str | None:
    """Path of the wheel-bundled .so, or None in a source checkout."""
    cand = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "librelayrl_native.so")
    return cand if os.path.isfile(cand) else None


def find_library() -> str | None:
    # Wheel install: the .so ships inside the package (setup.py builds
    # it into relayrl_tpu/_native/ — reference parity with its
    # maturin-bundled native artifact). Checked first so an installed
    # user never silently downgrades; source checkouts fall through to
    # the make -C native output.
    bundled = bundled_library_path()
    if bundled is not None:
        return bundled
    here = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for name in _LIB_NAMES:
        for cand in (os.path.join(here, "native", name),
                     os.path.join(here, name)):
            if os.path.isfile(cand):
                return cand
    return None
