"""The second third of the SLT corpus (see tests/test_slt.py: the corpus
is split over three files so `--dist loadfile` can spread it)."""

import pytest

from .test_slt import check_slt_file, coord, slt_params  # noqa: F401


@pytest.mark.parametrize(**slt_params(1))
def test_slt_file(path, coord):  # noqa: F811
    check_slt_file(path, coord)
