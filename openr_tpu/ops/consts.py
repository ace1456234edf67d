"""Shared compute-plane constants that must be importable WITHOUT jax.

``BIG`` is the effectively-infinite f32 distance used by every SPF
kernel (device and host mirrors).  It lives here as a plain Python
float — defining it as a ``jnp`` scalar at module scope (as ops.spf
once did) forces PJRT backend initialization at *import* time, which
claims the device and drags the device stack into scalar-only
deployments whose contract is "jax never loads" (Decision's native
what-if path).
"""

import numpy as np

#: effectively-infinite distance, exactly representable in f32 so the
#: device kernels and the numpy mirrors agree bit-for-bit
BIG = float(np.float32(3.4e38))
