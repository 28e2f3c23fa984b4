"""Share (%) of the device's busy time under the step program's
``serve.prefill_chunk`` scope: the chunk forward, its ``flash_prefill``
calls among it."""

import metrics_common as mc


def read(record):
    return mc.scope_share(record, "serve.prefill_chunk")
