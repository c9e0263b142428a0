"""What a model says of its own arithmetic, so that one reader serves
every model (benchmarks/readers/model.py). A configuration's helper (the
module its file names under "model") holds one `Yardsticks` under the
name YARDSTICKS; for a configuration with no "model" key the driver its
traffic file names holds it. The functions it names live in the model's
`*_ops.py`, beside peaks.py, each the LEAST a correct step must move or
compute, from shapes and the traffic file and never from what the
program does.

A later model adds a helper and an ops file and appends its cell's name
to the `workloads` of the entries it can answer; it edits no reader. A
kind of yardstick it leaves out reads None, and the line leaves the
metric out.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

DISPATCH = "rayt.engine.decode_dispatch"
CHUNK = "rayt.engine.prefill_chunk"


def per_shapes(fn: Callable) -> Callable:
    """`fn(config, shapes)` as a `flops_per_token(config, traffic)`, for
    a traffic file that gives its cycle as explicit `shapes`."""
    return lambda config, traffic: fn(config, traffic["shapes"])


@dataclasses.dataclass(frozen=True)
class Yardsticks:
    # (config, traffic file) -> required operations a token served
    flops_per_token: Callable | None = None
    # {"decode": [scope, ...], "prefill": [...]}: the scopes under which
    # the model's attention runs, by the phase of the step
    attn_scopes: dict | None = None
    # groups of fields of the decode_dispatch spans that count what the
    # decode rounds' queries attend to; each group is summed over the
    # traced stretch and handed, in order, to
    # decode_attn_work(config, *sums) -> (bytes, operations)
    decode_attended: tuple = ()
    decode_attn_work: Callable | None = None
    # the same of the prefill_chunk spans, pairs of query and visible
    # key, for prefill_attn_flops(config, *sums) -> operations
    prefill_visible: tuple = ()
    prefill_attn_flops: Callable | None = None
    # fields of the decode_dispatch spans: (what a step was asked to
    # read, what its queries attend to)
    cache_read: tuple | None = None

    def phase_scopes(self, phase: str) -> list:
        return [phase + "/" + s
                for s in (self.attn_scopes or {}).get(phase, ())]

    def reads(self) -> dict:
        """The scopes and span fields the generic readers read for this
        model, in the form of tests/benchmark_rehearsal/hand_made/*.json."""
        dispatch = [f for group in self.decode_attended for f in group]
        for side in self.cache_read or ():
            dispatch += side
        chunk = [f for group in self.prefill_visible for f in group]
        fields = {span: list(dict.fromkeys(names)) for span, names in
                  ((DISPATCH, dispatch), (CHUNK, chunk)) if names}
        return {"scopes": self.phase_scopes("decode")
                + self.phase_scopes("prefill"), "fields": fields}
