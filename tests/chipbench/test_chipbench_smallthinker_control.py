"""`smallthinker_decode_mixed16k`'s control and planted faults at the
rehearsal sizes on the CPU (a global and a window layer, window 8): the program reads
inside the rehearsal's limits, and the reference in fp8, the altered token
and each of the configuration's three faults (window layers attending every
key, global layers rotated, the router reading the feed-forward's input)
come out NOT correct against the same limits."""
from chipbench import compare, control, manifest

CELL_NAME = "smallthinker_decode_mixed16k"
CELL = manifest.Cell(manifest.load_manifest(), CELL_NAME)
LIMITS = {**CELL.traffic["limits"], **CELL.traffic["rehearse"]["limits"]}
CASES = ("control_fp8", "fault_token_altered", "fault_full_window",
         "fault_rope_global", "fault_router_after")


def test_the_program_passes_and_the_control_and_every_fault_fail():
    rows = control.read(CELL_NAME, 2147483714, True)
    assert set(rows) == {"program", *CASES}
    assert compare.judge(rows["program"], LIMITS)[0]
    for case in CASES:
        assert not compare.judge(rows[case], LIMITS)[0], (case, rows[case])
