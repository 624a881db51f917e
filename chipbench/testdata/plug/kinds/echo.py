"""A job kind that touches no program, for the test that a new kind of job is
new files only: it doubles a vector drawn from the seed, two callers at once.
Not used by any cell."""

import time

import numpy as np

from chipbench import jobs


class Echo:
    def __init__(self, config, mix, seed, spans):
        self.spans = spans
        self.keys = list(range(int(mix["keys"])))
        self.order = jobs.order(len(self.keys), seed)
        self.width = int(config["width"])
        self.values = np.random.default_rng(seed).standard_normal(
            (len(self.keys), self.width))

    def setup(self):
        pass

    def job(self, i):
        key = self.order[i % len(self.order)]
        with self.spans.span("job.echo"):
            time.sleep(0.002)
        return key, 1, {"value": self.values[key] * 2.0}

    def release(self):
        pass

    def work(self):
        return {"bytes": 16 * self.width, "flops": self.width}

    def references(self, keys, wrong=0.0):
        return {k: {"value": self.values[k] + self.values[k] + wrong}
                for k in keys}

    def gaps(self, answer, ref):
        return {"answer_gap":
                float(np.max(np.abs(answer["value"] - ref["value"])))}


def make(config, mix, seed, spans):
    return Echo(config, mix, seed, spans)


def numbers(config):
    return ("answer_gap",)


def controls(config):
    return {"control_off_by_a_thousandth": {"wrong": 1e-3}}


def planted_faults(config):
    return {}
