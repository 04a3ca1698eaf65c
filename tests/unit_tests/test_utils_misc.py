"""safe_import placeholders, first-rank ordering, compile-cache config."""

import pytest


def test_safe_import_success_and_failure():
    from automodel_tpu.utils.safe_import import safe_import, safe_import_from

    ok, np_mod = safe_import("numpy")
    assert ok and np_mod.asarray([1]).shape == (1,)

    ok, missing = safe_import("definitely_not_a_module_xyz")
    assert not ok
    assert not missing  # falsy placeholder
    with pytest.raises(ImportError, match="definitely_not_a_module_xyz"):
        missing.anything
    with pytest.raises(ImportError):
        missing()

    ok, fn = safe_import_from("numpy", "asarray")
    assert ok and fn([2]).shape == (1,)
    ok, bad = safe_import_from("numpy", "no_such_symbol_abc")
    assert not ok
    with pytest.raises(ImportError, match="no_such_symbol_abc"):
        bad()


def test_first_rank_first_single_process():
    from automodel_tpu.utils.dist_utils import first_rank_first

    with first_rank_first() as is_leader:
        assert is_leader  # single process is always the leader
