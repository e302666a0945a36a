from .se3_transformer import SE3Transformer, SE3TransformerModule
from .token_decoder import TokenDecoder
from .hybrid_decoder import HybridDecoder
