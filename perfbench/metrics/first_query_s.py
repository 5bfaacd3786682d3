"""compile + executable cache: the first ``collect_arrow()`` of the
process, on the host clock (compile, or the read of the persistent cache,
and the first upload)."""


def read(run):
    return run.get("first_query_s")
