"""95th percentile of what the driver thread lost between two steps, over
the window's `engine.between_steps` spans that found work waiting when they
opened (`had_work`): duration less `slept_us`. The tail that a device trace
shows as idle gaps under no span."""

from perfbench.lib.stats import percentile
from perfbench.lib.token_path import reading


def read(run):
    r = reading(run)
    if not r or not r["driver"]["gaps_with_work_us"]:
        return None
    return percentile(r["driver"]["gaps_with_work_us"], 95) / 1e3
