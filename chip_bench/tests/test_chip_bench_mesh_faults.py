"""The cell whose client axis is sharded over four chips, at the tiny
root's size and held to its committed limits, in a child process with
four host devices (set before JAX starts there): the harness reports
`correct` true for the sound program and false for each fault planted
under it, the exchange between chips left out among them."""
import os
import subprocess
import sys

import chip_bench_tiny
import pytest

SEED = 2**31 + 91


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chip_bench_tiny.make_root(tmp_path_factory.mktemp("tiny"))


def test_mesh_cell_faults_are_not_correct(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = os.path.dirname(os.path.abspath(__file__))
    p = subprocess.run(
        [sys.executable, os.path.join(here, "chip_bench_faults.py"),
         str(root), chip_bench_tiny.MESH, str(SEED), "sound", "unchanged",
         "half_left_out", "no_exchange", "altered"],
        env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = {ln.split()[1]: ln.split()[2] for ln in p.stdout.splitlines()
           if ln.startswith("FAULT ")}
    assert got == {"sound": "True", "unchanged": "False",
                   "half_left_out": "False", "no_exchange": "False",
                   "altered": "False"}, p.stdout
