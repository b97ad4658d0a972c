from .llama import LlamaConfig, LlamaForCausalLM, init_llama_params, llama_apply


def _zoo():
    """name → (config, factory). Factories take (config) and honor
    ``init_empty_weights`` (shapes only, no memory)."""
    z = {
        "llama2-7b": (LlamaConfig.llama2_7b(), lambda c: LlamaForCausalLM.from_config(c)),
        "llama2-13b": (
            LlamaConfig(
                hidden_size=5120, intermediate_size=13824, num_hidden_layers=40,
                num_attention_heads=40, num_key_value_heads=40,
            ),
            lambda c: LlamaForCausalLM.from_config(c),
        ),
        "llama2-70b": (
            LlamaConfig(
                hidden_size=8192, intermediate_size=28672, num_hidden_layers=80,
                num_attention_heads=64, num_key_value_heads=8,
            ),
            lambda c: LlamaForCausalLM.from_config(c),
        ),
        "tiny-llama": (LlamaConfig.tiny(), lambda c: LlamaForCausalLM.from_config(c)),
    }
    try:
        from .gpt2 import GPT2Config, GPT2LMHeadModel

        z["gpt2"] = (GPT2Config(), lambda c: GPT2LMHeadModel.from_config(c))
        z["gpt2-xl"] = (
            GPT2Config(hidden_size=1600, num_hidden_layers=48, num_attention_heads=25),
            lambda c: GPT2LMHeadModel.from_config(c),
        )
    except ImportError:
        pass
    try:
        from .bert import BertConfig, BertForSequenceClassification

        z["bert-base"] = (
            BertConfig(),
            lambda c: BertForSequenceClassification.from_config(c),
        )
    except ImportError:
        pass
    try:
        from .mixtral import MixtralConfig, MixtralForCausalLM

        z["mixtral-8x7b"] = (MixtralConfig(), lambda c: MixtralForCausalLM.from_config(c))
    except ImportError:
        pass
    try:
        from .granite_hybrid import GraniteHybridConfig, GraniteHybridForCausalLM

        z["granite-4.0-h-micro"] = (
            GraniteHybridConfig(), lambda c: GraniteHybridForCausalLM.from_config(c)
        )
    except ImportError:
        pass
    try:
        from .t5 import T5Config, T5ForConditionalGeneration

        z["t5-small"] = (T5Config.t5_small(), lambda c: T5ForConditionalGeneration.from_config(c))
        z["t5-base"] = (T5Config.t5_base(), lambda c: T5ForConditionalGeneration.from_config(c))
        z["t5-11b"] = (T5Config.t5_11b(), lambda c: T5ForConditionalGeneration.from_config(c))
    except ImportError:
        pass
    try:
        from .opt import OPTConfig, OPTForCausalLM

        z["opt-125m"] = (OPTConfig(), lambda c: OPTForCausalLM.from_config(c))
        z["opt-1.3b"] = (OPTConfig.opt_1_3b(), lambda c: OPTForCausalLM.from_config(c))
        z["opt-6.7b"] = (OPTConfig.opt_6_7b(), lambda c: OPTForCausalLM.from_config(c))
        z["opt-13b"] = (OPTConfig.opt_13b(), lambda c: OPTForCausalLM.from_config(c))
        z["opt-30b"] = (OPTConfig.opt_30b(), lambda c: OPTForCausalLM.from_config(c))
    except ImportError:
        pass
    try:
        from .gpt_neox import GPTNeoXConfig, GPTNeoXForCausalLM

        z["pythia-1.4b"] = (
            GPTNeoXConfig.pythia_1_4b(),
            lambda c: GPTNeoXForCausalLM.from_config(c),
        )
        z["gpt-neox-20b"] = (
            GPTNeoXConfig.neox_20b(),
            lambda c: GPTNeoXForCausalLM.from_config(c),
        )
        z["gpt-j-6b"] = (
            GPTNeoXConfig.gptj_6b(),
            lambda c: GPTNeoXForCausalLM.from_config(c),
        )
    except ImportError:
        pass
    try:
        from .resnet import ResNetConfig, ResNetForImageClassification

        z["resnet50d"] = (
            ResNetConfig.resnet50d(),
            lambda c: ResNetForImageClassification.from_config(c),
        )
    except ImportError:
        pass
    try:
        from .vit import ViTConfig, ViTForImageClassification

        z["vit-base-patch16-224"] = (
            ViTConfig.vit_b16(),
            lambda c: ViTForImageClassification.from_config(c),
        )
    except ImportError:
        pass
    return z


def __getattr__(name):
    # built lazily: zoo construction imports every model module
    if name == "MODEL_ZOO":
        return _zoo()
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


#: the ``model_type`` values :func:`config_from_hf_json` maps
KNOWN_MODEL_TYPES = (
    "llama", "mistral", "gpt2", "bert", "vit", "opt", "gpt_neox", "gptj", "mixtral",
    "t5", "mt5", "granitemoehybrid", "lfm2_moe", "sdar_moe", "deepseek_v3", "smallthinker",
)


def config_from_hf_json(path: str):
    """Map an HF-transformers ``config.json`` onto a zoo config by
    ``model_type`` (keeps the reference's 'point at any checkpoint' UX).
    What the zoo cannot build as published is refused, not approximated."""
    import dataclasses
    import json

    with open(path) as f:
        d = json.load(f)
    mt = d.get("model_type", "llama")
    if mt in ("llama", "mistral"):
        if d.get("sliding_window") is not None and d.get("use_sliding_window", True):
            # every layer of models/llama.py attends the whole context; a
            # published window is not dropped in silence
            raise ValueError(
                f"{mt} with sliding_window {d['sliding_window']!r}: models/llama.py attends "
                "the whole context in every layer and declares one cache kind, so the model "
                "as published would be served as another one. A window kind exists "
                "(models/cache.py:PagedKind, as models/smallthinker.py declares it); this "
                "family is not on it yet. Set sliding_window to null to serve the "
                "full-attention model (what Mistral-7B-v0.3 publishes)")
        return LlamaConfig(
            head_dim=d.get("head_dim"),
            vocab_size=d.get("vocab_size", 32000),
            hidden_size=d.get("hidden_size", 4096),
            intermediate_size=d.get("intermediate_size", 11008),
            num_hidden_layers=d.get("num_hidden_layers", 32),
            num_attention_heads=d.get("num_attention_heads", 32),
            num_key_value_heads=d.get("num_key_value_heads", d.get("num_attention_heads", 32)),
            max_position_embeddings=d.get("max_position_embeddings", 4096),
            rope_theta=d.get("rope_theta", 10000.0),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
        )
    if mt == "gpt2":
        from .gpt2 import GPT2Config

        return GPT2Config(
            vocab_size=d.get("vocab_size", 50257),
            hidden_size=d.get("n_embd", 768),
            num_hidden_layers=d.get("n_layer", 12),
            num_attention_heads=d.get("n_head", 12),
            max_position_embeddings=d.get("n_positions", 1024),
        )
    if mt == "bert":
        from .bert import BertConfig

        return BertConfig(
            vocab_size=d.get("vocab_size", 30522),
            hidden_size=d.get("hidden_size", 768),
            intermediate_size=d.get("intermediate_size", 3072),
            num_hidden_layers=d.get("num_hidden_layers", 12),
            num_attention_heads=d.get("num_attention_heads", 12),
            max_position_embeddings=d.get("max_position_embeddings", 512),
            type_vocab_size=d.get("type_vocab_size", 2),
            norm_eps=d.get("layer_norm_eps", 1e-12),
        )
    if mt == "vit":
        from .vit import ViTConfig

        return ViTConfig(
            image_size=d.get("image_size", 224),
            patch_size=d.get("patch_size", 16),
            in_channels=d.get("num_channels", 3),
            hidden_size=d.get("hidden_size", 768),
            num_hidden_layers=d.get("num_hidden_layers", 12),
            num_attention_heads=d.get("num_attention_heads", 12),
            intermediate_size=d.get("intermediate_size", 3072),
            layer_norm_eps=d.get("layer_norm_eps", 1e-6),
        )
    if mt == "opt":
        from .opt import OPTConfig

        if d.get("word_embed_proj_dim", d.get("hidden_size", 768)) != d.get(
            "hidden_size", 768
        ):
            raise ValueError(
                "OPT checkpoints with word_embed_proj_dim != hidden_size "
                "(opt-350m) are not supported"
            )
        return OPTConfig(
            vocab_size=d.get("vocab_size", 50272),
            hidden_size=d.get("hidden_size", 768),
            intermediate_size=d.get("ffn_dim", 3072),
            num_hidden_layers=d.get("num_hidden_layers", 12),
            num_attention_heads=d.get("num_attention_heads", 12),
            max_position_embeddings=d.get("max_position_embeddings", 2048),
        )
    if mt == "gpt_neox":
        from .gpt_neox import GPTNeoXConfig

        return GPTNeoXConfig(
            vocab_size=d.get("vocab_size", 50432),
            hidden_size=d.get("hidden_size", 768),
            intermediate_size=d.get("intermediate_size", 3072),
            num_hidden_layers=d.get("num_hidden_layers", 12),
            num_attention_heads=d.get("num_attention_heads", 12),
            max_position_embeddings=d.get("max_position_embeddings", 2048),
            rotary_pct=d.get("rotary_pct", 0.25),
            rope_theta=d.get("rotary_emb_base", 10000.0),
            use_parallel_residual=d.get("use_parallel_residual", True),
        )
    if mt == "gptj":
        from .gpt_neox import GPTNeoXConfig

        h = d.get("n_embd", 4096)
        heads = d.get("n_head", 16)
        return GPTNeoXConfig(
            vocab_size=d.get("vocab_size", 50400),
            hidden_size=h,
            intermediate_size=d.get("n_inner") or 4 * h,
            num_hidden_layers=d.get("n_layer", 28),
            num_attention_heads=heads,
            max_position_embeddings=d.get("n_positions", 2048),
            rotary_pct=d.get("rotary_dim", 64) / (h // heads),
            shared_layernorm=True,
            attention_bias=False,
        )
    if mt == "mixtral":
        from .mixtral import MixtralConfig

        return MixtralConfig(
            vocab_size=d.get("vocab_size", 32000),
            hidden_size=d.get("hidden_size", 4096),
            intermediate_size=d.get("intermediate_size", 14336),
            num_hidden_layers=d.get("num_hidden_layers", 32),
            num_attention_heads=d.get("num_attention_heads", 32),
            num_key_value_heads=d.get("num_key_value_heads", 8),
            num_local_experts=d.get("num_local_experts", 8),
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
        )
    if mt in ("t5", "mt5"):
        from .t5 import T5Config

        return T5Config(
            vocab_size=d.get("vocab_size", 32128),
            hidden_size=d.get("d_model", 512),
            d_kv=d.get("d_kv", 64),
            d_ff=d.get("d_ff", 2048),
            num_layers=d.get("num_layers", 6),
            num_decoder_layers=d.get("num_decoder_layers", d.get("num_layers", 6)),
            num_heads=d.get("num_heads", 8),
            relative_attention_num_buckets=d.get("relative_attention_num_buckets", 32),
            relative_attention_max_distance=d.get("relative_attention_max_distance", 128),
            feed_forward_proj=(
                "gated-gelu" if "gated" in d.get("feed_forward_proj", "relu") else "relu"
            ),
            tie_word_embeddings=d.get("tie_word_embeddings", True),
        )
    if mt == "granitemoehybrid":
        from .granite_hybrid import GraniteHybridConfig

        if d.get("num_local_experts", 0):
            raise ValueError(
                f"granitemoehybrid with num_local_experts {d['num_local_experts']}: the "
                "expert layer is not built here, only the dense shared MLP "
                "(num_local_experts 0, as granite-4.0-h-micro)"
            )
        heads, groups = d.get("mamba_n_heads", 64), d.get("mamba_n_groups", 1)
        if groups < 1 or heads % groups:
            raise ValueError(
                f"mamba_n_groups {groups} does not divide mamba_n_heads {heads}"
            )
        if d.get("position_embedding_type", "nope") != "nope":
            raise ValueError(
                f"position_embedding_type {d['position_embedding_type']!r}: the attention "
                "layers are built without a position term ('nope'), as published"
            )
        fields = {f.name for f in dataclasses.fields(GraniteHybridConfig)} - {"remat"}
        return GraniteHybridConfig(**{k: d[k] for k in fields if d.get(k) is not None})
    if mt == "lfm2_moe":
        from .lfm2 import Lfm2MoeConfig

        # what the file leaves out is the published model's; what it states and
        # cannot be built (a third layer kind, more dense layers than layers, more
        # choices than experts, conv_bias) is refused by the config itself
        fields = {f.name for f in dataclasses.fields(Lfm2MoeConfig)} - {"remat"}
        return Lfm2MoeConfig(**{k: d[k] for k in fields if d.get(k) is not None})
    if mt == "sdar_moe":
        from .sdar_moe import SdarMoeConfig

        # every layer is a routed one and attends every position: what the
        # published file can say otherwise is refused, not approximated
        for key, built in (("mlp_only_layers", []), ("decoder_sparse_step", 1),
                           ("attention_bias", False), ("use_sliding_window", False),
                           ("rope_scaling", None), ("hidden_act", "silu")):
            if d.get(key, built) != built:
                raise ValueError(
                    f"sdar_moe with {key} {d[key]!r}: built as published for "
                    f"SDAR-30B-A3B-Chat, {key} {built!r}"
                )
        fields = {f.name for f in dataclasses.fields(SdarMoeConfig)} - {"remat"}
        return SdarMoeConfig(**{k: d[k] for k in fields if d.get(k) is not None})
    if mt == "deepseek_v3":
        from .deepseek_v3 import DeepseekV3Config

        # the published keys by their names; what cannot be built as published (a
        # scoring_func other than sigmoid, a topk_method other than noaux_tc, a
        # rope_scaling type other than yarn, ...) is refused by the config itself.
        # num_nextn_predict_layers (the multi-token-prediction block) and
        # num_key_value_heads (latent attention has no kv head) are read by nothing
        fields = {f.name for f in dataclasses.fields(DeepseekV3Config)} - {"remat"}
        return DeepseekV3Config(**{k: d[k] for k in fields if d.get(k) is not None})
    if mt == "smallthinker":
        from .smallthinker import SmallThinkerConfig

        # the published keys by their names; what cannot be built as published
        # (a sigmoid router, layouts of another length than the depth, a window
        # layer first) is refused by the config itself. Keys for the "secondary
        # experts" the model card speaks of are not in the published file
        for key, built in (("rope_scaling", None), ("attention_bias", False),
                           ("hidden_act", "relu"), ("moe_enable_secondary_experts", False)):
            if d.get(key, built) not in (built, None):
                raise ValueError(
                    f"smallthinker with {key} {d[key]!r}: built as published for "
                    f"SmallThinker-21BA3B-Instruct, {key} {built!r}")
        fields = {f.name for f in dataclasses.fields(SmallThinkerConfig)} - {"remat"}
        return SmallThinkerConfig(**{k: d[k] for k in fields if d.get(k) is not None})
    raise ValueError(
        f"unsupported model_type {mt!r} (known: {', '.join(KNOWN_MODEL_TYPES)})"
    )


def model_factory_for_config(config):
    """``factory(config)`` for a zoo config; the causal LMs that can be
    served also take ``from_config``'s keywords (``seed``, ``dtype``)."""
    name = type(config).__name__
    if name == "LlamaConfig":
        return lambda c, **kw: LlamaForCausalLM.from_config(c, **kw)
    if name == "GraniteHybridConfig":
        from .granite_hybrid import GraniteHybridForCausalLM

        return lambda c, **kw: GraniteHybridForCausalLM.from_config(c, **kw)
    if name == "Lfm2MoeConfig":
        from .lfm2 import Lfm2MoeForCausalLM

        return lambda c, **kw: Lfm2MoeForCausalLM.from_config(c, **kw)
    if name == "SdarMoeConfig":
        from .sdar_moe import SdarMoeForCausalLM

        return lambda c, **kw: SdarMoeForCausalLM.from_config(c, **kw)
    if name == "DeepseekV3Config":
        from .deepseek_v3 import DeepseekV3ForCausalLM

        return lambda c, **kw: DeepseekV3ForCausalLM.from_config(c, **kw)
    if name == "SmallThinkerConfig":
        from .smallthinker import SmallThinkerForCausalLM

        return lambda c, **kw: SmallThinkerForCausalLM.from_config(c, **kw)
    if name == "GPT2Config":
        from .gpt2 import GPT2LMHeadModel

        return lambda c: GPT2LMHeadModel.from_config(c)
    if name == "OPTConfig":
        from .opt import OPTForCausalLM

        return lambda c: OPTForCausalLM.from_config(c)
    if name == "GPTNeoXConfig":
        from .gpt_neox import GPTNeoXForCausalLM

        return lambda c: GPTNeoXForCausalLM.from_config(c)
    if name == "MixtralConfig":
        from .mixtral import MixtralForCausalLM

        return lambda c: MixtralForCausalLM.from_config(c)
    if name == "BertConfig":
        from .bert import BertForSequenceClassification

        return lambda c: BertForSequenceClassification.from_config(c)
    if name == "T5Config":
        from .t5 import T5ForConditionalGeneration

        return lambda c: T5ForConditionalGeneration.from_config(c)
    if name == "ResNetConfig":
        from .resnet import ResNetForImageClassification

        return lambda c: ResNetForImageClassification.from_config(c)
    if name == "ViTConfig":
        from .vit import ViTForImageClassification

        return lambda c: ViTForImageClassification.from_config(c)
    raise ValueError(f"no factory for {name}")
