from .serialization import save_params, load_params
from .helpers import (
    exists, default, uniq, to_order, map_values, safe_cat, cast_tuple,
    batched_index_select, masked_mean, fourier_encode, broadcat, benchmark,
    masked_fill,
)
