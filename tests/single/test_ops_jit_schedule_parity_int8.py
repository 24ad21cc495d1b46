"""Quantized allreduce: ``int8``'s three ring schedules against the exact
fp32 psum, one schedule a case and the psum made once a module.  One codec a
file (the bodies are ``_jit_helpers.exact_payload`` and
``schedule_parity_case``, the same for both): ``--dist loadfile`` gives a
file to one pytest-xdist worker."""

import pytest

from _jit_helpers import exact_payload, schedule_parity_case

pytestmark = pytest.mark.usefixtures("hvd_single")

CODEC, QMAX = "int8", 127.0


@pytest.fixture(scope="module")
def payload():
    return exact_payload(QMAX)


@pytest.mark.parametrize("schedule", ["ring", "bidi", "torus"])
def test_schedule_differential_parity_exact(payload, schedule):
    schedule_parity_case(CODEC, *payload, schedule)
