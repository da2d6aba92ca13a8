"""The control has to come out as not correct: the reference computed in
float8 (the nearest precision below the bfloat16 the configurations state),
put in the program's place and read as the program is read, fails at least
one of each cell's numbers. Read here at fira-tiny; the readings at the
cells' own sizes are in PERF.md."""

from bench_tiny import run_cell


def _fails(numbers, limits):
    from benchmark import check

    return not check.judge(numbers, limits)["correct"]


def test_training_control_and_half_batch_fail():
    res = run_cell("train", extra=("control", "half_batch"))
    limits = {k: v["limit"] for k, v in res["check"].items()}
    extra = res["info"]["extra_numbers"]
    assert res["correct"]
    assert _fails(extra["control"], limits)
    assert _fails(extra["half_batch"], limits)


def test_decode_control_fails():
    for traffic in ("drain", "serve"):
        res = run_cell(traffic, extra=("control",))
        limits = {k: v["limit"] for k, v in res["check"].items()}
        control = res["info"]["extra_numbers"]["control_fp8"]
        assert res["correct"], traffic
        assert control["_where"]["positions"] >= 40
        assert _fails(control, limits), traffic
