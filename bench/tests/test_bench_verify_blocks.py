"""The verify kernel's live-block share: the scheduler's page-block
counters read into ``verify_live_block_share.serve``."""
import importlib.util
import os

import pytest

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_verify_live_block_share_reader():
    read = _reader("verify_live_block_share.serve")
    counters = {"prefill_tokens": 900, "verify_page_blocks_live": 96,
                "verify_page_blocks_total": 512}
    assert read({"counters": counters}) == pytest.approx(18.75)
    # a program that keeps no such counters: nothing to read
    assert read({"counters": {"prefill_tokens": 900}}) is None
