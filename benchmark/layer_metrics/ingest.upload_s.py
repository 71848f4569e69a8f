"""Ingest layer: seconds from the start of the first scan of each of the
cell's tables until its last batch was on the device (gauge
ingest_upload_ns_total, plan/overrides.py): dictionary encoding and the
host->device copies. The program waits for the copies on a thread beside
the plan, so this time overlaps the first programs' load or compile; it
lies in set-up, inside the first query of compile.first_query_s."""


def read(ctx):
    from spark_rapids_tpu.obs import gauges
    ns = gauges.snapshot().get("ingest_upload_ns_total")
    return None if ns is None else ns / 1e9
