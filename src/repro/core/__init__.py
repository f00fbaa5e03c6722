# SpeedyFeed — the paper's primary contribution, as a composable JAX module.
from .plm import DecoderConfig, PLMConfig, additive_attention, init_plm
from .buslm import (buslm_encode, decoder_encode, decoder_encode_set,
                    plm_flops)
from .cache import (CacheConfig, CachePlan, CacheState, assemble_embeddings,
                    cache_plan, cache_refresh, init_cache)
from .centralized import MergedSet, dispatch, gather_dedup
from .user_model import (UserModelConfig, attentive_user,
                         attentive_user_causal, init_user_model,
                         user_embeddings)
from .loss import ar_loss, click_loss, sample_negatives
from .pipeline import (SpeedyFeedConfig, StepOut, conventional_forward,
                       init_speedyfeed, make_config, speedyfeed_forward,
                       speedyfeed_state)
