from . import batching, graph, news_synth, recsys_synth, refine, tokenizer
from .batching import (EPOCH_END, DynamicBatcher, LoaderConfig, NewsStore,
                       build_centralized_batch, build_conventional_batch,
                       default_buckets, pad_segments,
                       synth_centralized_batch)
from .news_synth import (ClickLog, NewsCorpus, click_share_topk,
                         make_click_log, make_corpus)
from .refine import CorpusStats, build_corpus_stats, obow, refine, refined_tokens
