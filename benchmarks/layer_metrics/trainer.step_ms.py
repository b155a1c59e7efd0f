"""Trainer: host clock around a step closed by `block_until_ready`,
median over the window's steps, ms."""

from lib import stats


def read(metric, m):
    return stats.percentile(m.get("step_ms", []), 50)
