"""Built-in predictors.

* ``T5GenerativePredictor`` — the generative-inference predictor of the
  primary workload (the ``HuggingFaceModelPredictor`` analog, reference
  predictor.py:14-106): pulls model/tokenizer/preprocessor from a Checkpoint,
  runs the jit-compiled autoregressive ``generate`` on device, decodes to a
  ``generated_output`` column.  TPU-first: inputs go through a single
  host→HBM transfer, decode runs as a compiled ``lax.scan`` with a KV cache
  (no per-token Python), and dtype morphing (bf16) happens at param load.
* ``JaxPredictor`` — generic forward-pass predictor for any Flax model
  (``TorchPredictor`` analog, Scaling_batch_inference.ipynb:cc-71).
* ``GBDTPredictor`` — the ``XGBoostPredictor`` analog
  (Introduction_to_Ray_AI_Runtime.ipynb:cc-57) over the host-side sklearn
  gradient-boosting model produced by ``GBDTTrainer``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np
import pandas as pd

from tpu_air.predict.predictor import Predictor


def _checkpoint_tokenizer(checkpoint, tokenizer):
    """The tokenizer a generative predictor decodes with: the instance
    given, else the checkpoint's (loaded as the class given, if one was).
    A checkpoint trained on token ids carries none; generation then returns
    id strings."""
    if tokenizer is not None and not isinstance(tokenizer, type):
        return tokenizer
    try:
        return checkpoint.get_tokenizer(tokenizer)
    except FileNotFoundError:
        return None


class T5GenerativePredictor(Predictor):
    """Batched text generation from a T5 checkpoint (predictor.py:14-106 analog)."""

    def __init__(self, model, params, tokenizer=None, preprocessor=None):
        super().__init__(preprocessor)
        self.model = model
        self.params = params
        self.tokenizer = tokenizer

    @classmethod
    def from_checkpoint(
        cls,
        checkpoint,
        *,
        model_cls=None,
        tokenizer=None,
        dtype: Optional[str] = None,
        sharding=None,
        use_tpu: bool = True,
        **_: Any,
    ) -> "T5GenerativePredictor":
        """Build from a Checkpoint.  ``dtype="bfloat16"`` is the TPU analog of
        the reference's fp16 load (Model_finetuning…ipynb:cc-64); ``sharding``
        is the ``device_map="auto"`` analog — an explicit jax.sharding spec."""
        model, params = checkpoint.get_model(model_cls=model_cls, dtype=dtype, sharding=sharding)
        if dtype:
            import jax
            import jax.numpy as jnp

            params = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.dtype(dtype)) if hasattr(x, "astype") else x, params
            )
        return cls(model, params, _checkpoint_tokenizer(checkpoint, tokenizer),
                   checkpoint.get_preprocessor())

    def _predict_numpy(
        self,
        data: Dict[str, np.ndarray],
        feature_columns: Optional[List[str]] = None,
        max_new_tokens: int = 128,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        **_: Any,
    ) -> pd.DataFrame:
        from tpu_air.models.t5.generate import generate

        if feature_columns:
            data = {k: v for k, v in data.items() if k in feature_columns}
        input_ids = np.asarray(data["input_ids"])
        mask = data.get("attention_mask")
        seqs = generate(
            self.model,
            self.params,
            input_ids,
            attention_mask=mask,
            max_new_tokens=max_new_tokens,
            do_sample=do_sample,
            temperature=temperature,
            top_k=top_k,
        )
        seqs = np.asarray(seqs)
        if self.tokenizer is not None:
            texts = self.tokenizer.batch_decode(seqs, skip_special_tokens=True)
        else:
            texts = [" ".join(map(str, row)) for row in seqs]
        return pd.DataFrame({"generated_output": texts})


class LMGenerativePredictor(Predictor):
    """Batched text generation from a causal-LM checkpoint (LMTrainer
    output) — the decoder-only sibling of :class:`T5GenerativePredictor`,
    so LM checkpoints compose with BatchPredictor / serve unchanged."""

    def __init__(self, model, params, tokenizer=None, preprocessor=None):
        super().__init__(preprocessor)
        self.model = model
        self.params = params
        self.tokenizer = tokenizer

    @classmethod
    def from_checkpoint(
        cls,
        checkpoint,
        *,
        tokenizer=None,
        dtype: Optional[str] = None,
        **_: Any,
    ) -> "LMGenerativePredictor":
        model, params = checkpoint.get_model(dtype=dtype)
        if dtype:
            import jax
            import jax.numpy as jnp

            params = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.dtype(dtype)) if hasattr(x, "astype") else x,
                params,
            )
        return cls(model, params, _checkpoint_tokenizer(checkpoint, tokenizer),
                   checkpoint.get_preprocessor())

    def _predict_numpy(
        self,
        data: Dict[str, np.ndarray],
        feature_columns: Optional[List[str]] = None,
        max_new_tokens: int = 64,
        do_sample: bool = False,
        temperature: float = 1.0,
        top_k: int = 0,
        seed: int = 0,
        **_: Any,
    ) -> pd.DataFrame:
        import jax

        from tpu_air.models.lm import generate

        if feature_columns:
            data = {k: v for k, v in data.items() if k in feature_columns}
        try:
            input_ids = np.asarray(
                np.stack([np.asarray(r) for r in data["input_ids"]])
            )
        except ValueError as e:
            raise ValueError(
                "LMGenerativePredictor needs EQUAL-LENGTH prompts per batch "
                "(the decode cache is positional): bucket rows by length "
                f"before predict ({e})"
            ) from None
        if (input_ids == self.model.config.pad_token_id).any():
            # padded prompts would feed pad tokens as real context and
            # sample the first token from a pad position's logits
            raise ValueError(
                "LMGenerativePredictor prompts must be un-padded; strip pad "
                "tokens and bucket rows to equal lengths"
            )
        # vary sampling noise across batches deterministically: fold a
        # per-predictor call counter into the seed
        self._calls = getattr(self, "_calls", 0) + 1
        rng = jax.random.fold_in(jax.random.PRNGKey(seed), self._calls)
        toks = np.asarray(generate(
            self.model, self.params, input_ids,
            max_new_tokens=max_new_tokens, do_sample=do_sample,
            temperature=temperature, top_k=top_k,
            eos_token_id=getattr(self.model.config, "eos_token_id", None),
            rng=rng,
        ))
        if self.tokenizer is not None:
            texts = self.tokenizer.batch_decode(toks, skip_special_tokens=True)
        else:
            texts = [" ".join(map(str, row)) for row in toks]
        return pd.DataFrame({"generated_output": texts})


class JaxPredictor(Predictor):
    """Generic forward-pass predictor: ``apply_fn(params, **features)``."""

    def __init__(self, apply_fn: Callable, params, preprocessor=None, output_column: str = "predictions"):
        super().__init__(preprocessor)
        self.apply_fn = apply_fn
        self.params = params
        self.output_column = output_column

    @classmethod
    def from_checkpoint(cls, checkpoint, *, apply_fn: Callable, dtype=None, **_: Any) -> "JaxPredictor":
        params = checkpoint.get_params(dtype=dtype)
        return cls(apply_fn, params, checkpoint.get_preprocessor())

    def _predict_numpy(self, data: Dict[str, np.ndarray], **kwargs) -> pd.DataFrame:
        out = self.apply_fn(self.params, **data, **kwargs)
        out = np.asarray(out)
        if out.ndim > 1 and out.shape[-1] == 1:
            out = out[..., 0]
        col = list(out) if out.ndim > 1 else out
        return pd.DataFrame({self.output_column: col})


class SemanticSegmentationPredictor(Predictor):
    """SegFormer batch-inference predictor (the reference's custom
    ``SemanticSegmentationPredictor`` analog,
    Scaling_batch_inference.ipynb:cc-73): feature-extract → jit forward →
    ``post_process_semantic_segmentation`` → per-image class maps.

    TPU-first: the forward pass is jit-compiled once per batch shape and runs
    NHWC on device; pre/post-processing stays host-side.
    """

    def __init__(self, model, params, batch_stats=None, feature_extractor=None,
                 preprocessor=None, output_column: str = "predicted_mask"):
        super().__init__(preprocessor)
        self.model = model
        self.params = params
        self.batch_stats = batch_stats or {}
        self.feature_extractor = feature_extractor
        self.output_column = output_column
        self._jit_forward = None

    @classmethod
    def from_checkpoint(
        cls,
        checkpoint,
        *,
        model_cls=None,
        feature_extractor=None,
        dtype: Optional[str] = None,
        **_: Any,
    ) -> "SemanticSegmentationPredictor":
        model, params = checkpoint.get_model(model_cls=model_cls, dtype=dtype)
        # _load_extras returns None for missing files; real load errors
        # (corrupt pickle etc.) must propagate — silently dropping
        # batch_stats would surface later as a confusing flax
        # missing-collection error inside the decode head's BatchNorm.
        extras = checkpoint._load_extras() or {}
        if feature_extractor is None:
            feature_extractor = extras.get("feature_extractor")
        if feature_extractor is None:
            from tpu_air.models.segformer import SegformerImageProcessor

            feature_extractor = SegformerImageProcessor()
        # NB: deliberately does NOT attach the checkpoint's train-time
        # preprocessor — the reference's segmentation predictor consumes raw
        # images and applies its feature extractor inside _predict_pandas
        # (Scaling_batch_inference.ipynb:cc-73); the fitted-preprocessor
        # auto-apply contract belongs to the tabular/text predictors.
        return cls(
            model,
            params,
            batch_stats=extras.get("batch_stats"),
            feature_extractor=feature_extractor,
        )

    def _forward(self, px):
        import jax
        import jax.numpy as jnp

        if self._jit_forward is None:
            variables = {"params": self.params}
            if self.batch_stats:
                variables["batch_stats"] = self.batch_stats
            # airlint: disable=JX003 — guarded by the None check above: the
            # lambda is created and jitted once, then memoized on self
            self._jit_forward = jax.jit(
                lambda x: self.model.apply(variables, x)
            )
        return self._jit_forward(jnp.asarray(px))

    def _predict_pandas(self, data: pd.DataFrame, **_: Any) -> pd.DataFrame:
        from tpu_air.models.segformer.image_processor import (
            _to_numpy_image,
            collate_pixel_batch,
        )

        if "pixel_values" in data.columns:
            px = collate_pixel_batch(data["pixel_values"])
            sizes = [tuple(px.shape[1:3])] * len(px)
        else:
            col = "image" if "image" in data.columns else data.columns[0]
            # normalize layout first — raw CHW arrays would otherwise yield
            # (channels, height) target sizes
            images = [_to_numpy_image(im) for im in data[col]]
            sizes = [im.shape[:2] for im in images]
            px = self.feature_extractor(images)["pixel_values"]
        logits = np.asarray(self._forward(px), np.float32)
        maps = self.feature_extractor.post_process_semantic_segmentation(
            logits, target_sizes=sizes
        )
        return pd.DataFrame({self.output_column: [m for m in maps]})


class GBDTPredictor(Predictor):
    """XGBoostPredictor analog: host-side GBDT scoring (Introduction…ipynb:cc-57)."""

    def __init__(self, model, preprocessor=None):
        super().__init__(preprocessor)
        self.model = model

    @classmethod
    def from_checkpoint(cls, checkpoint, **_: Any) -> "GBDTPredictor":
        model = checkpoint.get_model()
        if isinstance(model, tuple):  # (flax_model, params) — wrong checkpoint kind
            raise TypeError("checkpoint does not contain a GBDT/sklearn model")
        return cls(model, checkpoint.get_preprocessor())

    def _predict_pandas(self, data: pd.DataFrame, **_: Any) -> pd.DataFrame:
        X = data.to_numpy(dtype=np.float32)
        if hasattr(self.model, "predict_proba"):
            preds = self.model.predict_proba(X)[:, 1]
        else:
            preds = self.model.predict(X)
        return pd.DataFrame({"predictions": preds})


class SklearnPredictor(GBDTPredictor):
    """Alias family for generic sklearn estimators stored in checkpoints."""


#: Drop-in alias matching the reference import name (Introduction…ipynb:cc-57)
XGBoostPredictor = GBDTPredictor
