"""The benchmark's own tests of the KDA, latent-attention and group-limited
mixture family (``perfbench/tests/test_kda_mla_moe_family.py``: the rehearsal
cell through the whole harness, the controls' script, the cell's files), run
from the tier-1 lane: ``testpaths`` is ``tests``, and perfbench/tests has a
conftest of its own, so each runs in a pytest of its own."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FILE = os.path.join("perfbench", "tests", "test_kda_mla_moe_family.py")


@pytest.mark.parametrize("name", [
    "test_rehearsal_through_the_whole_harness",
    "test_every_control_is_read_and_parts_from_the_sound_reference",
    "test_the_cells_files_say_what_the_issue_asks"])
def test_the_family_in_the_benchmarks_own_tests(name):
    p = subprocess.run(
        [sys.executable, "-m", "pytest", f"{FILE}::{name}", "-x",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=ROOT, capture_output=True, text=True, timeout=1200,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0 and "1 passed" in p.stdout, \
        p.stdout[-3000:] + p.stderr[-2000:]
