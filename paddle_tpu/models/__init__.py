from .bert import (BertConfig, BertForPretraining,
                   BertForSequenceClassification, BertModel)
from .ernie import (ErnieConfig, ErnieForPretraining,
                    ErnieForSequenceClassification, ErnieModel, tp_annotate)
from .gpt import GPTConfig, GPTForCausalLM, GPTModel, MoEFeedForward
from .glm_moe import GlmMoeLiteConfig, GlmMoeLiteForCausalLM
from .falcon_h1 import FalconH1Config, FalconH1ForCausalLM
