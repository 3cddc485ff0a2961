"""pytest form of selfcheck.py (CPU backend): `pytest benchmarks/`."""

import pytest

import selfcheck


def test_trace_reduce_fixtures():
    selfcheck.check_trace_reduce()


def test_mix_with_parameters():
    selfcheck.check_params()


@pytest.mark.parametrize("cell", selfcheck.cells())
def test_cell_is_correct(cell):
    selfcheck.check_cell(cell)


@pytest.mark.parametrize("cell", selfcheck.cells())
def test_float32_control_is_not_correct(cell):
    selfcheck.check_control(cell)


@pytest.mark.parametrize("fault", selfcheck.FAULTS)
@pytest.mark.parametrize("cell", selfcheck.cells())
def test_altered_answer_is_not_correct(cell, fault):
    selfcheck.check_fault(cell, fault)
