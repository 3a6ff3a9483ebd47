"""Median time the ``Prefetcher``'s worker is busy for one batch of the
measured window: the library's ``input.produce`` span (the source: the
``Dataset``'s gather) plus ``input.transfer`` (``shard_batch``: the host's
share of the copy) of the same batch. It must stay under the step time, or
the loop waits. An earlier line gives the two parts, the host bytes a batch
and the share of batches that were ready when asked for."""
from chipbench import spans

UNIT = "ms"
LAYER = "input"
MOVES = "samples_per_s_chip"


def applies(cell):
    return spans.available()


def read(run):
    from horovod_tpu.core import telemetry
    produce, transfer, wait = (spans.window(run, "input." + part)
                               for part in ("produce", "transfer", "wait"))
    for p, x, w in zip(produce, transfer, wait):
        # one batch: gathered, then handed to the runtime, then handed out
        if not p[3] <= x[2] <= x[3] <= w[3]:
            raise SystemExit(
                f"chipbench: the input spans of batch {p[1]} are out of order "
                f"(produce ends {p[3]}, transfer {x[2]}..{x[3]}, wait ends "
                f"{w[3]}): 'seq' does not name one batch")
    counter = telemetry.active().registry.counter_value
    handed = counter("hvd_input_batches_total")
    run.info(what="input_produce",
             produce_ms=spans.median_ms(spans.seconds(produce)),
             transfer_ms=spans.median_ms(spans.seconds(transfer)),
             bytes_a_batch=counter("hvd_input_bytes_total")
             / len(spans.log("input.transfer")),
             ready_share=counter("hvd_input_ready_total") / handed)
    return spans.median_ms(
        a + b for a, b in zip(spans.seconds(produce), spans.seconds(transfer)))
