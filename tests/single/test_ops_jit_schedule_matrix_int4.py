"""Quantized allreduce: the ``int4`` device codec under every ring
schedule.  One codec a file (the body is ``_jit_helpers.codec_schedule_case``,
the same for the three): ``--dist loadfile`` gives a file to one pytest-xdist
worker, and all nine cases were the longest file of the gate."""

import pytest

from _jit_helpers import codec_schedule_case

pytestmark = pytest.mark.usefixtures("hvd_single")


@pytest.mark.parametrize("schedule", ["ring", "bidi", "torus"])
@pytest.mark.parametrize("codec", ["int4"])
def test_quantized_allreduce_codec_schedule_matrix(codec, schedule):
    codec_schedule_case(codec, schedule)
