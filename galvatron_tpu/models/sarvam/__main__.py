from galvatron_tpu.models.sarvam import main

raise SystemExit(main())
