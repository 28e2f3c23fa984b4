"""Share (%) of the device's busy time under the step program's
``serve.moe`` scope: routing, the grouped expert matmuls (their ``gmm``
calls among them), the combine and the shared experts."""

import metrics_common as mc


def read(record):
    return mc.scope_share(record, "serve.moe")
