"""The spread of a set of runs by the driver's two rules, on sets worked by hand."""
import json

import pytest

from benchmark import spread as S


@pytest.mark.parametrize("values,whole,trimmed", [
    # quantiles(n=4), exclusive: of six sorted values d0 + 0.75 (d1 - d0) and
    # d4 + 0.25 (d5 - d4); of five, the means of d0, d1 and of d3, d4
    ([10.0, 10.1, 10.2, 10.3, 10.4, 10.6], 0.375 / 10.25, 0.30 / 10.2),
    # one far-off run widens the whole set's spread and leaves the trimmed one alone
    ([10.0, 10.1, 10.2, 10.3, 10.4, 14.0], 1.225 / 10.25, 0.30 / 10.2),
    # two far-off runs do harm either way
    ([10.0, 10.1, 10.2, 10.3, 14.0, 14.2], 3.975 / 10.25, 2.1 / 10.2),
])
def test_spreads_follow_statistics_quantiles(values, whole, trimmed):
    assert S.quartile_spread(values) == pytest.approx(whole)
    assert S.trimmed_spread(values) == pytest.approx(trimmed)
    assert S.trimmed_spread(values[::-1]) == pytest.approx(trimmed)   # order is nothing


def test_the_tool_reads_result_lines(tmp_path, capsys):
    files = []
    for i, v in enumerate([14.9, 15.0, 15.1, 15.2, 15.3, 15.4]):
        line = {"correct": True, "failed": 0,
                "metrics": {"token_gap_p50_ms": {"value": v, "unit": "ms"}}}
        files.append(tmp_path / f"{i}.out")
        files[-1].write_text("a log line\n" + json.dumps(line) + "\n")
    assert S.main(["--metric", "token_gap_p50_ms", *map(str, files)]) == 0
    out = capsys.readouterr().out
    assert "median 15.1500" in out and "farthest run left out 1.9" in out
