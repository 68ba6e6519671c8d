"""What a per-layer metric's reader is given: one traced slice.

A reader (``metrics/<name>.py``) defines ``read(run: TracedSlice)``
and returns a number, or None where the slice holds nothing to read
(then the metric is left out of the line; a share of a roofline or of a
peak is never reported as 0 for want of work).  The quantities that
several readers report, each under the name of the cell's own
end-to-end metric it moves, are methods here.
"""

from __future__ import annotations

import dataclasses

import counts

ENGINE_SPANS = frozenset({"engine.start_cohort", "engine.step_cohort"})


@dataclasses.dataclass
class TracedSlice:
    reduction: object          # xplane.Reduction of the slice
    shape: counts.Shape        # the configuration's sizes
    peaks: dict                # the device's row of peaks.json
    chips: int
    calls: list                # serveloop.Call rows that ran in the slice
    spans: list                # (name, start, end) host spans in the slice
    decode_tokens: list        # cache positions of each token a decode
    #                            step made in the slice

    def calls_of(self, kind: str) -> list:
        return [c for c in self.calls if c.kind == kind]

    def self_seconds(self, parent: str, children: frozenset) -> list:
        """Per ``parent`` span: its duration less that of the
        ``children`` spans inside it."""
        kids = [s for s in self.spans if s[0] in children]
        out = []
        for name, a, b in self.spans:
            if name != parent:
                continue
            inner = sum(e - s for _, s, e in kids if a <= s and e <= b)
            out.append((b - a) - inner)
        return out

    def device_seconds_per_run(self, module: str):
        """Device seconds of one execution of ``module``, averaged over
        its executions and the chips; None when it did not run."""
        runs = self.reduction.module_runs.get(module, 0)
        if not runs:
            return None
        return self.reduction.module_s.get(module, 0.0) / runs

    def sched_host_ms(self):
        """Host milliseconds per scheduler iteration spent outside the
        engine's cohort calls: each ``sched.step`` span less the
        ``engine.*`` spans in it (admission, queue and cohort bookkeeping
        in ``serving/scheduler.py`` and ``serving/admission.py``),
        averaged over the iterations in the slice."""
        own = self.self_seconds("sched.step", ENGINE_SPANS)
        return 1e3 * sum(own) / len(own) if own else None

    def prefill_ms_per_ktok(self):
        """Device milliseconds of the prefill program (``jit_prefill``)
        per 1,000 prompt tokens prefilled in the slice, averaged over
        the chips."""
        prompt_tokens = sum(c.batch * c.context
                            for c in self.calls_of("prefill"))
        device_s = self.reduction.module_s.get("jit_prefill")
        if not prompt_tokens or not device_s:
            return None
        return device_s * 1e3 / (prompt_tokens / 1e3)

    def decode_device_ms(self):
        """Device milliseconds of one execution of the decode program
        (``jit_decode``), averaged over its executions in the slice and
        the chips."""
        per_run = self.device_seconds_per_run("jit_decode")
        return None if per_run is None else per_run * 1e3

    def decode_hbm_roofline(self):
        """Share of the HBM roofline a decode step reaches, in %: the
        bytes a step must read on one chip (its weights, the table rows
        it looks up and the KV cache of its rows; averaged over the
        decode calls in the slice) over the device time of one
        ``jit_decode`` execution at the chip's peak bandwidth."""
        calls = self.calls_of("decode")
        per_run = self.device_seconds_per_run("jit_decode")
        if not calls or per_run is None:
            return None
        need = sum(counts.decode_bytes_per_chip(self.shape, c.batch,
                                                [c.context] * c.batch)
                   for c in calls) / len(calls)
        return 100.0 * need / (per_run * self.peaks["hbm_bytes_per_s"])

    def mfu_decode(self):
        """Model FLOP/s utilisation of the decode step as the host sees
        it, in %: the FLOPs of the tokens decode made in the slice over
        the wall time of the ``step_cohort`` calls that made them (host
        work, launch and device time together) times the chips' bf16
        peak.  It bounds ``decode_hbm_roofline`` from the side of the
        whole step."""
        wall = sum(c.t1 - c.t0 for c in self.calls_of("decode"))
        if not wall or not self.decode_tokens:
            return None
        flops = sum(counts.decode_token_flops(self.shape, ctx)
                    for ctx in self.decode_tokens)
        return 100.0 * flops / (wall * self.chips
                                * self.peaks["bf16_flops_per_s"])

    def mfu(self):
        """Model FLOP/s utilisation of the whole step, in %: the FLOPs
        the model needs for the prompt tokens prefilled and the tokens
        decoded in the slice (no padding, no finished rows) over the
        slice's length times the chips' bf16 peak."""
        flops = sum(counts.prefill_flops(self.shape, c.batch, c.context)
                    for c in self.calls_of("prefill"))
        flops += sum(counts.decode_token_flops(self.shape, ctx)
                     for ctx in self.decode_tokens)
        if not flops:
            return None
        peak = self.chips * self.peaks["bf16_flops_per_s"]
        return 100.0 * flops / (self.reduction.window_s * peak)

    def idle_share(self):
        """Share of the slice in which no op ran on the device, in %: 1
        less the union of the op intervals over the slice, averaged over
        the chips."""
        return 100.0 * self.reduction.idle_share
