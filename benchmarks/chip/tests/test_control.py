"""The control: the reference one precision step down (float32 with the
three bf16 passes of ``Precision.HIGH``) fails every configuration's
``rel_err_limit``, while the float64 reference passes against itself."""

from __future__ import annotations

import pytest

import control


@pytest.mark.parametrize("config,events", [("smarthome_q25", 8000)])
def test_control_fails_the_limit(config, events):
    r = control.control_reading(config, 11, events)
    assert r["fails"], r
    assert r["max_rel_err"] > 3 * r["limit"], r
