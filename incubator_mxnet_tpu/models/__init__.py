"""Model families covering the BASELINE.json configs:

- lenet:      LeNet MNIST (config 1)
- resnet etc: via gluon.model_zoo.vision (config 2)
- bert:       BERT-base pretraining w/ TP + ring-attention SP (config 3)
- ssd:        SSD object detection w/ MultiBox ops (config 4)
- lstm_lm:    LSTM language model (config 5)
- olmoe:      OLMoE decoder (RoPE + QK-norm attention, dropless SwiGLU MoE)
- nemotron_h: Nemotron-H hybrid (Mamba-2, grouped-query attention, a latent
              mixture of relu^2 experts), whole or as one chip's share
- phi4flash:  Phi-4-mini-flash / SambaY (Mamba-1, sliding-window and full
              differential attention, a cross-decoder of gated memory units
              and attention over ONE layer's K/V, dense SwiGLU MLPs)
- solar_open2: Solar Open 2 (Kimi Delta Attention: a gated delta rule with a
              decay per channel; gated grouped-query attention without
              position embedding; sigmoid-routed SwiGLU experts beside a
              shared expert), whole or as one chip's share
- ling3:      Ling 3.0 (Kimi Delta Attention with full-rank maps and a
              bounded decay, five layers to one of multi-head latent
              attention: one latent and one rotary key a position for all
              heads; a leading dense SwiGLU layer; sigmoid-routed experts
              chosen under a group limit beside a shared expert), whole or
              as one chip's share of its experts
- xing4:      Xing 4.0 (a residual path of several streams a position,
              mixed around every sublayer by per-token maps of which the
              square one is made doubly stochastic by Sinkhorn rounds;
              multi-head latent attention with a low-rank query and YaRN
              frequencies in every layer; a leading dense SwiGLU layer;
              sigmoid-routed experts beside a shared expert), whole or as
              one chip's share of its experts
- keye_vl2:   Keye-VL 2.0's decoder (grouped-query attention over the keys a
              lightning indexer picks, the indexer's own KL loss, rotary
              positions from three streams, softmax-routed SwiGLU experts),
              whole or as one chip's share of its experts
- evabyte:    EvaByte (EVA attention: exact causal keys of a query's own
              aligned window and one learned summary a chunk of every
              earlier window under one softmax; a float32 residual stream;
              a head that predicts the next eight bytes of every position)
"""
from .lenet import LeNet  # noqa
from .bert import (BERTEncoder, BERTModel, TransformerEncoderLayer,  # noqa
                   MultiHeadAttention, ChunkedMLMLoss)
from .gpt import (GPTModel, TransformerDecoderLayer, ChunkedLMLoss,  # noqa
                  FeaturesView)
from .olmoe import (OLMoEModel, OLMoETransformerDecoderLayer,  # noqa
                    RotaryMultiHeadAttention, ChunkedUntiedLMLoss)
from .nemotron_h import (NemotronHModel, NemotronHLayer, Mamba2Mixer,  # noqa
                         GroupedQueryAttention, LatentMoE)
from .phi4flash import (Phi4FlashModel, SambaYLayer, Mamba1Mixer,  # noqa
                        DifferentialAttention, GatedMemoryUnit, SwiGLU)
from .solar_open2 import (SolarOpen2Model, SolarOpen2Layer,  # noqa
                          KimiDeltaAttention, GatedGroupedQueryAttention,
                          SharedExpertMoE)
from .ling3 import Ling3Model, MultiHeadLatentAttention  # noqa
from .xing4 import Xing4Model, Xing4Layer, HyperConnection  # noqa
from .keye_vl2 import (KeyeVL2Model, KeyeVL2Layer,  # noqa
                       SparseGroupedQueryAttention)
from .evabyte import (EvaByteModel, EvaByteLayer, EvaAttention,  # noqa
                      UnitOffsetRMSNorm, RowBlockedSwiGLU, MultiByteLoss)
from .lstm_lm import LSTMLanguageModel  # noqa
from .ssd import SSD  # noqa
from ..gluon.model_zoo.vision import get_model  # noqa
