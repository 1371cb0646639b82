"""Store (``core/delta.py`` RegionStore): device milliseconds per epoch of
its programs: normalize, the eager re-insertion probe, the commit folds
and compaction."""

STORE = {"_normalize_core", "_any_member", "_commit_fold", "_commit_fold_impl",
         "_compact_fold"}


def read(obs):
    if not obs.devices or obs.epochs <= 0:
        return None
    return 1e3 * obs.module_seconds(lambda m: m in STORE) / obs.epochs
