"""Each cell's code path at a tiny CPU size, through the harness's own
functions: the Pallas kernels in interpret mode, the real configurations
and mixes cut down (``chipbench_tiny``).  A sound run is correct; the
program's lower-precision path (the control) is not."""

import pytest

import chipbench_tiny as tiny


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench_cache")


def test_train_cell_tiny_is_correct(cache):
    out, ctx = tiny.run_cell("kingsnake-4m-512-train", "steady", cache)
    assert tiny.correct(out), out["checks"]
    assert out["metrics"]["train_step_s"] > 0
    assert ctx.notes["window_steps"] >= 1
    assert ctx.notes["steps_built"] == 1          # one compiled step
    # every leaf but the rotations of isotropic splats is compared
    assert ctx.notes["leaves_compared_for_change"] == [
        "means", "log_scales", "opacity_logit", "colors"]


@pytest.mark.parametrize("mix", ["orbit", "revisit"])
def test_serve_cell_tiny_is_correct(cache, mix):
    out, ctx = tiny.run_cell("kingsnake-4m-512-serve", mix, cache)
    assert tiny.correct(out), out["checks"]
    assert out["failed"] == 0
    assert ctx.notes["completed"] == ctx.notes["requests"] > 0
    tel = out["telemetry"]
    if mix == "revisit":                 # the primed cache holds every pose
        assert tel["misses"] == 0 and tel["hits"] == ctx.notes["requests"]
    else:
        assert tel["misses"] > 0


@pytest.mark.parametrize("config,mix", [
    ("kingsnake-4m-512-train", "steady"),
    ("kingsnake-4m-512-serve", "orbit")])
def test_control_bf16_is_not_correct(cache, config, mix):
    out, _ = tiny.run_cell(config, mix, cache, impl="ref",
                           overrides={"dtype_policy": "bf16"})
    assert not tiny.correct(out), out["checks"]


def test_sample_holds_a_far_request_a_hit_and_each_slot():
    import numpy as np

    import open_loop
    import serve_cell
    reqs = [open_loop.Request(float(i), np.eye(4, dtype=np.float32), i == 5)
            for i in range(40)]
    kept = {i: (None, 0, 64, False, False, 1 if i in (7, 30) else 0)
            for i in range(40) if i != 3}
    for seed in range(20):
        s = serve_cell.draw_sample(reqs, kept, [12], 4, seed)
        assert len(s) == 4 and {5, 12} <= s and s & {7, 30}
    assert serve_cell.draw_sample(reqs, kept, [], 50, 1) == set(range(40))
