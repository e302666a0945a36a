from .fiber import Fiber, FiberEl, fiber_of
from .core import (
    LinearSE3, NormSE3, FeedForwardSE3, FeedForwardBlockSE3, residual_se3,
)
from .conv import ConvSE3, RadialFunc, pairwise_conv_contract
from .attention import AttentionSE3, OneHeadedKVAttentionSE3, AttentionBlockSE3
from .egnn import EGNN, EGnnNetwork, HtypesNorm
from .neighbors import (
    exclude_self_indices, remove_self, expand_adjacency,
    sparse_neighbor_mask, select_neighbors, Neighborhood,
)
from .rotary import (
    sinusoidal_embeddings, apply_rotary_pos_emb, rotary_angles,
    apply_rotary_halves,
)
from .trunk import SequentialTrunk
