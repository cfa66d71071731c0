"""Per-layer metric ``round_handoff_idle_share``.

Of the traced rounds' time, the device idle that ``program_trace`` charges
to the round's phases other than ``round.generate`` and to what runs under
them (the learn step's dispatch and read, the push) and to the root's own
time: the two hand-offs, last decode step to first learn operation and push
to first prefill.  Idle inside ``round.generate`` is the engine's own.
"""

import program_trace

NAME = "round_handoff_idle_share"
UNIT = "%"
LAYER = "round"
MOVES = "rollout_tokens_per_s"

_ROOT = "scalerl.genrl.round"
# the spans open while the engine generates: idle there is not a hand-off
_GENERATE = {
    "scalerl.round.generate", "scalerl.genrl.macro_step", "scalerl.genrl.admit",
    "scalerl.genrl.dispatch", "scalerl.genrl.read", "scalerl.genrl.harvest",
}


def read(r):
    program = program_trace.of(r)
    if program is None or r["trace"] is None or not program.devices:
        return None
    rounds_s = sum(program.durations_ms(_ROOT)) / 1e3
    if rounds_s <= 0.0:
        return None
    idle = sum(
        s for name, s in program.idle_by_span.items()
        if name.startswith(program_trace.PREFIX) and name not in _GENERATE
    )
    r["ctx"].log(
        f"{NAME}: {idle:.4f} s of device idle outside generation in {rounds_s:.3f} s of traced rounds"
    )
    return 100.0 * idle / rounds_s
