"""cohort_build.host_ms: host milliseconds of one cohort build
(data/cohort_source.CohortSource.cohort), timed inline after the window on
rounds the run has not used; the mean over the builds."""
import statistics


def read(ctx):
    """Mean milliseconds of one inline cohort build."""
    builds = ctx["cohort_build_s"]
    if not builds:
        return None
    return 1e3 * statistics.mean(builds)
