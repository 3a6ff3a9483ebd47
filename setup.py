"""Build hooks for horovod-tpu.

Metadata lives in pyproject.toml; this file only adds the native build:
``hvd_runtime.cc`` → ``horovod_tpu/native/_build/libhvd_runtime_<hash>.so``
via the same cached g++ invocation the lazy in-tree path uses
(horovod_tpu/native/build.py), so a wheel ships the prebuilt library while
a source checkout still self-compiles on first import. Reference parity:
setup.py + CMakeLists compile-the-core-at-install-time (SURVEY.md §2.5),
minus the per-framework matrix (one backend here).

The build degrades gracefully: no C++ toolchain → pure-python wheel (the
native layer is an accelerator for host-side work, never a requirement),
matching the reference's HOROVOD_WITHOUT_* escape hatches.
"""

import os
import sys

from setuptools import setup
from setuptools.command.build_py import build_py


class BuildWithNative(build_py):
    def run(self):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        try:
            from horovod_tpu.native import build as native_build
            lib = native_build.build()
            if lib:
                print(f"built native runtime: {lib}")
            else:
                print("no C++ toolchain; shipping pure-python package")
        except Exception as e:  # never fail the install on native issues
            print(f"native build skipped: {e}")
        super().run()


setup(cmdclass={"build_py": BuildWithNative})
