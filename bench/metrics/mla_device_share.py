"""Share (%) of the device's busy time under the step program's
``serve.mla`` scope: the latent attention's projections, the
absorption of ``W_UK`` and ``W_UV``, the latent pool's writes and the
attention kernels over it."""

import metrics_common as mc


def read(record):
    return mc.scope_share(record, "serve.mla")
