"""The benchmark's files agree with one another (`benchmark/file_cases.py`:
every name in `BENCHMARK.json` and in a configuration has its file, every
`reduced` key its reason, no cell offers a device twice in a window)."""

import pytest

from benchmark.file_cases import CASES


@pytest.mark.parametrize(
    "check, subject", CASES, ids=[f"{c.__name__}[{s}]" for c, s in CASES])
def test_benchmark_files_agree(check, subject):
    check(subject)
