"""Each configuration's control, put in the program's place, comes out not
correct, and the reference itself comes out correct, at a size a test run
holds (on the chip, ``run.py --control`` does the same at the cell's own
size)."""
import pytest

from cpu_cells import REPO, config
from benchlib import cells, check, oracle, program
from benchlib.stream import Repeated


@pytest.mark.parametrize("name", ["yelp_reviews_csv", "nyc_taxi_csv"])
@pytest.mark.parametrize("seed", [1, 2**31 + 3, 77])
def test_control_fails_and_reference_passes(name, seed):
    cfg = dict(config(name), distinct_bytes=300_000)
    schema = program.schema_of(cfg)
    gen = cells.load_module(REPO, "gen", cfg["generator"]["name"])
    block, rec_end, _ = gen.make(seed, cfg["distinct_bytes"], cfg["generator"])
    control = cells.load_module(REPO, "controls", cfg["control"])
    stream = Repeated(block, rec_end)
    pb = 65536
    limits = cfg["limits"]
    for k, final in ((0, False), (3, False), (7, True)):
        data = stream.partition(k, pb, 8 * pb, final)
        records = oracle.parse(data)
        ref = check.compare(check.reference_view(records, schema), records,
                            schema, final)
        assert ref.mismatches == 0 and ref.float_rel_gap == 0.0
        ctl = check.compare(control.view(data, schema), records, schema, final)
        assert (ctl.mismatches > limits["mismatches"]
                or ctl.float_rel_gap > limits["float_rel_gap"]), ctl
