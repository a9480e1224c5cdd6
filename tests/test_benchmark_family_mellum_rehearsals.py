"""The whole rehearsal of the family `mellum`'s cell
(`benchmark/tests/test_family_mellum.py`) and the first of its faulted ones,
collected apart from `test_benchmark_family_mellum.py` so that they run on a
worker of their own; the other two faulted rehearsals are in
`test_benchmark_family_mellum_faults.py`."""

from benchmark.tests.test_family_mellum import (    # noqa: F401
    rehearsal_limits,
    test_a_whole_rehearsal_is_correct_and_reads_its_metrics,
    test_a_ring_written_one_cell_off_is_not_correct)


def test_the_decode_heavy_cell_rehearses_whole(capsys, tmp_path, rehearsal_limits,
                                               monkeypatch):
    """`serve-mellum2-decode-64` (PR 33): the same configuration under
    `decode-closed-64`, listed under tokens/s and the per-layer metrics that
    move it (PERF.md 2: on six seeds the gap spread 1.2 % and the time to the
    first token 3.5 %, which the check does not admit)."""
    from benchmark.tests import test_family_mellum as family

    monkeypatch.setattr(family, "CELL", "serve-mellum2-decode-64")
    cell = family.run.load_cell(family.CELL, True)
    assert (cell.cell["config"], cell.cell["traffic"], cell.cell["chips"]) == (
        "mellum2-12b-a2.5b-pp8", "decode-closed-64", 1)
    ends = {m["name"] for m in family.run.metrics_of(cell.bench, "end_to_end", family.CELL)}
    assert ends == {"serve_tokens_per_s", "setup_s"}
    layers = {m["name"] for m in family.run.metrics_of(cell.bench, "per_layer", family.CELL)}
    ling = {m["name"] for m in family.run.metrics_of(
        cell.bench, "per_layer", "serve-ling3-decode-64") if m["moves"] == "serve_tokens_per_s"}
    assert layers == ling >= {"decode.step_mfu", "decode.step_roofline", "moe.experts_hit_share"}
    line, dumped = family.rehearsal(capsys, tmp_path)
    assert line["correct"] is True and line["failed"] == 0
    assert line["compiles_in_window"] == 0
    assert 0 < line["metrics"]["moe.experts_hit_share"]["value"] <= 100
    assert line["metrics"]["batcher.admit_wall_share"]["value"] > 0
    assert set(line["metrics"]) <= layers
    # the rehearsal's 4 slots stay with the sorted form (8 experts, top 2: 68 %)
    assert dumped["counters"]["slots"] == 4
