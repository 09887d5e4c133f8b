"""Median over the window's pieces of piece seconds / passes (host clock
around the fetched result): the per-piece statistic that stands beside the
whole-window rate."""

import statistics


def read(run):
    per_pass = [(p["t1"] - p["t0"]) / p["passes"] * 1e3
                for p in run.window["pieces"] if p["passes"] > 0]
    return statistics.median(per_pass) if per_pass else None
