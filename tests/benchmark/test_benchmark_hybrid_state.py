"""``nemotron_group_rollout``'s limit on the recurrent state's handoff, at
the rehearsal's size on the CPU: it passes the program and refuses a
reference with a planted fault."""

from bench_helpers import result_line, run_cell, workload_file


def test_the_hybrid_cell_refuses_a_state_taken_at_the_buckets_end():
    """``nemotron_group_rollout``'s rehearsal: the tokens decoded right
    after the recurrent state was handed over (two whole groups: a leader
    from its prefill's state, three members from the fork's rows) read
    within their limit, and the reference with the planted fault (the
    recurrence run on through the prompt's pads) reads a hundred times
    over it: the limit has teeth where the whole-response medians have
    none."""
    cell = "nemotron_group_rollout"
    notes = result_line(run_cell(cell, 0, "--rehearse"))["notes"]
    limits = workload_file(cell)["rehearse_params"]
    assert notes["state_handoff_ok"] is True and notes["state_rows_checked"] == 8
    assert min(notes["state_pads"]) > 0  # a prompt that fills its bucket plants nothing
    for kind in ("logp", "value"):
        limit = limits[f"state_{kind}_median_atol"]
        assert notes[f"state_{kind}_median_err"] <= limit
        assert notes[f"pad_fault_state_{kind}_median_err"] > 100 * limit
