"""The driver's multi-chip dry-run must always work on the virtual CPU mesh
(conftest forces 8 devices)."""

import importlib.util
import pathlib

import pytest


def _load():
    p = pathlib.Path(__file__).parent.parent / "__graft_entry__.py"
    spec = importlib.util.spec_from_file_location("graft_entry", p)
    m = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(m)
    return m


@pytest.mark.slow  # ~51s compile grid; the 2-device variant below keeps
# every dry-run phase (dp/sp/tp, MoE ep, pipeline, v2) in tier-1
def test_dryrun_multichip_8():
    _load().dryrun_multichip(8)


def test_dryrun_multichip_2():
    _load().dryrun_multichip(2)
