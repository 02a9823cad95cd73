"""paddle_tpu.nn — layers and functional ops (reference python/paddle/nn)."""
from . import functional  # noqa: F401
from . import initializer  # noqa: F401
from .layer import Layer, Parameter, ParamAttr  # noqa: F401
from .container import Sequential, LayerList, LayerDict, ParameterList  # noqa: F401
from .common import (  # noqa: F401
    Identity, Linear, GatedFFN, PlainFFN, Embedding, Dropout, Dropout2D, Dropout3D, AlphaDropout,
    Flatten, Upsample, UpsamplingBilinear2D, UpsamplingNearest2D,
    PixelShuffle, Pad1D, Pad2D, Pad3D, CosineSimilarity, Bilinear,
    ReLU, ReLU6, LeakyReLU, ELU, CELU, SELU, GELU, Silu, Swish, Mish,
    Hardswish, Hardsigmoid, Hardtanh, Hardshrink, Softshrink, Tanhshrink,
    Softplus, Softsign, Sigmoid, LogSigmoid, Tanh, Softmax, LogSoftmax,
    ThresholdedReLU, Maxout, PReLU,
)
from .conv import (  # noqa: F401
    Conv1D, Conv2D, Conv3D, Conv1DTranspose, Conv2DTranspose,
    Conv3DTranspose,
)
from .norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, SyncBatchNorm,
    LayerNorm, RMSNorm, GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
    LocalResponseNorm, SpectralNorm,
)
from .pooling import (  # noqa: F401
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D,
    AdaptiveMaxPool1D, AdaptiveMaxPool2D, AdaptiveMaxPool3D,
)
from .loss import (  # noqa: F401
    CrossEntropyLoss, NLLLoss, BCELoss, BCEWithLogitsLoss, MSELoss, L1Loss,
    SmoothL1Loss, KLDivLoss, MarginRankingLoss, HingeEmbeddingLoss,
    CosineEmbeddingLoss, TripletMarginLoss, CTCLoss,
)
from .transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
from .rnn import (  # noqa: F401
    RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN, BiRNN, SimpleRNN,
    LSTM, GRU,
)
from .decode import (  # noqa: F401
    Decoder, BeamSearchDecoder, dynamic_decode, DecodeHelper,
    TrainingHelper, GreedyEmbeddingHelper, SampleEmbeddingHelper,
    BasicDecoder,
)
from .clip import ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm  # noqa: F401
from .moe import (  # noqa: F401
    MoELayer, SparseMoELayer, moe_apply_ep, MOE_EP_RULES,
)
from .linear_attention import GatedDeltaNet, KimiDeltaAttention  # noqa: F401
from .grouped_query_attention import GroupedQueryAttention  # noqa: F401
from .state_space import Mamba2Mixer  # noqa: F401
from .gated_conv import GatedShortConv  # noqa: F401
from .latent_attention import MLAttention  # noqa: F401
from .crf import LinearChainCRF, crf_decoding, linear_chain_crf  # noqa: F401,E402

# 2.0-alpha surface parity: pre-rename spellings + functional re-exports
# + the layers that only lived there (must import LAST — it fills gaps
# without overriding anything above)
from . import compat20  # noqa: F401,E402
